"""§Perf feature tests: bf16 wire format, hlo_analysis loop awareness,
dry-run config overrides."""

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch


def test_bf16_wire_apss_exact(corpus, mesh4x2):
    """bf16 blocks travel as u16; matches stay within bf16 tolerance of
    the f32 oracle (bound: one bf16 rounding of the inputs)."""
    from repro.core.apss import apss_reference
    from repro.core.distributed import apss_2d
    from repro.core.graph import match_set

    D32 = jnp.asarray(corpus)
    D16 = D32.astype(jnp.bfloat16)
    # threshold far from any true similarity: rounding can't flip matches
    ref = apss_reference(D32, 0.35, 16)
    got = jax.jit(
        lambda d: apss_2d(d, 0.35, 16, mesh4x2, accumulation="allreduce",
                          block_rows=16)
    )(D16)
    a, b = match_set(got), match_set(ref)
    # allow borderline flips only (|sim - t| < bf16 eps·scale)
    diff = a ^ b
    assert len(diff) <= max(2, len(b) // 50), (len(diff), len(b))


def test_hlo_analysis_loop_multiplication():
    from repro.launch.hlo_analysis import analyze

    def loop(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, None, length=7)[0]

    sds = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    hlo = jax.jit(loop).lower(sds, sds).compile().as_text()
    a = analyze(hlo)
    assert a["flops"] == pytest.approx(7 * 2 * 128 ** 3, rel=0.01)
    assert a["max_multiplier"] >= 7


def test_dryrun_overrides_parse():
    from repro.launch.dryrun import apply_overrides

    cfg = get_arch("apss").make_config()
    out = apply_overrides(cfg, ["dtype=bf16", "block_rows=256"])
    assert out["dtype"] == "bf16" and out["block_rows"] == 256
    assert out["wikipedia"] == cfg["wikipedia"] and cfg["block_rows"] == 512
    d = apply_overrides({"a": 1}, ["a=2", "b=x"])
    assert d == {"a": 2, "b": "x"}
