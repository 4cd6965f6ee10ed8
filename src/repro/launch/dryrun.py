import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # placeholder devices; never the chip

"""Multi-pod dry-run: lower + compile every APSS cell (``configs/apss_paper.py``)
on the production mesh and extract the roofline terms from the compiled
artifact.

MUST be executed as its own process (``python -m repro.launch.dryrun``): the
two environment lines above run before any other import so jax initializes
with 512 placeholder host devices on the CPU platform. Its ``--subprocess``
children inherit both, so neither the driver nor a child ever takes an
attached accelerator. Smoke tests / benches never import this module.

Per cell it records (JSON under --out):
  - compile wall time, per-device memory_analysis (args/outputs/temps)
  - per-device HLO FLOPs + bytes accessed (cost_analysis)
  - per-collective link-byte accounting parsed from the post-SPMD HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute, ring algorithm factors, replica-group aware)
  - the three roofline terms (v5e: 197 TF/s bf16, 819 GB/s HBM,
    50 GB/s/link ICI) and the dominant term.

Usage:
  python -m repro.launch.dryrun --list
  python -m repro.launch.dryrun --arch apss --shape grid_2d
  python -m repro.launch.dryrun --arch apss --shape grid_2d --multi-pod
  python -m repro.launch.dryrun --all [--multi-pod] [--subprocess]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

# --- hardware constants (TPU v5e) ---
PEAK_FLOPS = 197e12        # bf16 per chip
HBM_BW = 819e9             # bytes/s per chip
ICI_BW = 50e9              # bytes/s per link (~per-chip per-direction)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _shape_bytes(tok: str) -> int:
    m = _SHAPE_RE.match(tok)
    if not m:
        return 0
    dt, dims = m.groups()
    if dt not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dt]


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))  # [n_groups, group_size]<=[...]
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 1


# `%name = RESULT_TYPE op-name(...)` — operands are printed as %refs without
# types in optimized HLO, so byte accounting uses the RESULT type(s).
_OP_LINE_RE = re.compile(
    r"=\s*(?P<result>\([^)]*\)|\S+)\s+"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?P<variant>-start|-done)?\("
)


def collective_stats(hlo_text: str) -> dict:
    """Per-kind (count, link_bytes, payload_bytes) from post-SPMD HLO.

    Ring-algorithm link factors (per participating chip):
      all-gather      (g-1)/g · S_result
      all-reduce      2(g-1)/g · S_result
      reduce-scatter  (g-1)/g · (S_result · g)   (= input size)
      all-to-all      (g-1)/g · S_result
      permute         1 · S_result
    ``-done`` lines are skipped (their ``-start`` was already counted; for
    async starts the output buffer is the last tuple element).
    """
    stats = {
        k: {"count": 0, "link_bytes": 0.0, "payload_bytes": 0.0}
        for k in _COLLECTIVES
    }
    for line in hlo_text.splitlines():
        m = _OP_LINE_RE.search(line)
        if not m:
            continue
        kind, variant = m.group("kind"), m.group("variant")
        if variant == "-done":
            continue
        shapes = [
            _shape_bytes(t)
            for t in re.findall(r"\w+\[[\d,]*\]", m.group("result"))
        ]
        shapes = [s for s in shapes if s > 0]
        if not shapes:
            continue
        if variant == "-start" and len(shapes) > 1:
            # async start result = (input buf(s), output buf(s), ...)
            payload = shapes[len(shapes) // 2] if kind != "all-reduce" else shapes[-1]
        else:
            payload = sum(shapes)
        g = max(_group_size(line), 1)
        if kind == "all-gather":
            link = payload * (g - 1) / g
        elif kind == "all-reduce":
            link = 2 * payload * (g - 1) / g
        elif kind == "reduce-scatter":
            link = payload * (g - 1)  # = (payload·g)·(g-1)/g
        elif kind == "all-to-all":
            link = payload * (g - 1) / g
        else:  # collective-permute
            link = payload
        stats[kind]["count"] += 1
        stats[kind]["link_bytes"] += link
        stats[kind]["payload_bytes"] += payload
    return stats


def roofline_terms(flops: float, hbm_bytes: float, link_bytes: float) -> dict:
    compute = flops / PEAK_FLOPS
    memory = hbm_bytes / HBM_BW
    collective = link_bytes / ICI_BW
    dominant = max(
        ("compute", compute), ("memory", memory), ("collective", collective),
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "dominant": dominant,
    }


def _parse_override(kv: str):
    k, v = kv.split("=", 1)
    for cast in (int, float):
        try:
            return k, cast(v)
        except ValueError:
            pass
    if v in ("True", "true"):
        return k, True
    if v in ("False", "false"):
        return k, False
    return k, v


def apply_overrides(cfg, overrides: list):
    """``key=value`` overrides onto dataclass or dict configs (§Perf)."""
    import dataclasses

    kv = dict(_parse_override(o) for o in overrides)
    if not kv:
        return cfg
    if isinstance(cfg, dict):
        out = dict(cfg)
        out.update(kv)
        return out
    return dataclasses.replace(cfg, **kv)


def run_cell(
    arch_name: str, shape_name: str, *, multi_pod: bool, overrides: list = ()
) -> dict:
    import jax
    from repro.configs import get_arch
    from repro.launch.mesh import make_production_mesh

    arch = get_arch(arch_name)
    cell = arch.cell(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size

    cfg = apply_overrides(arch.make_config(), list(overrides))
    t0 = time.time()
    build = cell.build(cfg, mesh)
    jitted = jax.jit(
        build.fn,
        in_shardings=build.in_shardings,
        out_shardings=build.out_shardings,
    )
    lowered = jitted.lower(*build.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()

    # Loop-aware per-device accounting (XLA cost_analysis counts while
    # bodies once — see hlo_analysis module docstring).
    from repro.launch.hlo_analysis import analyze

    han = analyze(hlo)
    colls = han["collectives"]
    link_bytes = han["link_bytes"]
    flops = han["flops"]
    hbm_bytes = han["hbm_bytes"]
    terms = roofline_terms(flops, hbm_bytes, link_bytes)

    model_flops = build.static_info.get("model_flops", 0)
    result = {
        "arch": arch_name,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "status": "ok",
        "t_lower_s": round(t_lower, 2),
        "t_compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
            "total_bytes": (
                mem.argument_size_in_bytes
                + mem.output_size_in_bytes
                + mem.temp_size_in_bytes
            ),
        },
        "per_device_flops": flops,
        "per_device_hbm_bytes": hbm_bytes,
        "per_device_link_bytes": link_bytes,
        "collectives": colls,
        "xla_cost_analysis": {
            "flops_body_once": float(cost.get("flops", 0.0)),
            "bytes_accessed_body_once": float(cost.get("bytes accessed", 0.0)),
        },
        "hlo_analysis_meta": {
            "n_computations": han["n_computations"],
            "max_loop_multiplier": han["max_multiplier"],
        },
        "roofline": terms,
        "static_info": {
            k: v for k, v in build.static_info.items() if not callable(v)
        },
        "model_flops_total": model_flops,
        "model_flops_per_device": model_flops / chips if chips else 0,
        "useful_flops_ratio": (
            (model_flops / chips) / flops if flops else 0.0
        ),
    }
    return result


def cell_list() -> list:
    from repro.configs import get_arch

    return [("apss", s) for s in get_arch("apss").shapes]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--subprocess", action="store_true",
                    help="run each cell in an isolated subprocess (with --all)")
    ap.add_argument("--timeout", type=int, default=3600)
    ap.add_argument("--override", action="append", default=[],
                    help="config overrides key=value (perf variants)")
    ap.add_argument("--tag", default="",
                    help="suffix for the output json (perf variants)")
    args = ap.parse_args()

    if args.list:
        for a, s in cell_list():
            print(f"{a:24s} {s}")
        return

    os.makedirs(args.out, exist_ok=True)

    def out_path(a, s, mp):
        mesh = "2x16x16" if mp else "16x16"
        tag = f"__{args.tag}" if args.tag else ""
        return os.path.join(args.out, f"{a}__{s}__{mesh}{tag}.json")

    if args.all:
        failures = []
        for a, s in cell_list():
            path = out_path(a, s, args.multi_pod)
            if os.path.exists(path):
                print(f"[dryrun] skip (cached): {a} × {s}")
                continue
            if args.subprocess:
                cmd = [
                    sys.executable, "-m", "repro.launch.dryrun",
                    "--arch", a, "--shape", s, "--out", args.out,
                ] + (["--multi-pod"] if args.multi_pod else [])
                print(f"[dryrun] {' '.join(cmd[3:])}")
                r = subprocess.run(cmd, timeout=args.timeout)
                if r.returncode != 0:
                    failures.append((a, s))
            else:
                try:
                    res = run_cell(a, s, multi_pod=args.multi_pod)
                    with open(path, "w") as f:
                        json.dump(res, f, indent=1)
                    print(_summary(res))
                except Exception:
                    traceback.print_exc()
                    failures.append((a, s))
        if failures:
            print("FAILURES:", failures)
            sys.exit(1)
        print("[dryrun] all cells compiled OK")
        return

    assert args.arch and args.shape, "--arch/--shape or --all required"
    res = run_cell(
        args.arch, args.shape, multi_pod=args.multi_pod,
        overrides=args.override,
    )
    if args.tag:
        res["variant"] = {"tag": args.tag, "overrides": args.override}
    with open(out_path(args.arch, args.shape, args.multi_pod), "w") as f:
        json.dump(res, f, indent=1)
    print(_summary(res))
    print(json.dumps(res["collectives"], indent=1))


def _summary(res: dict) -> str:
    r = res["roofline"]
    gb = res["memory"]["total_bytes"] / 2**30
    return (
        f"[dryrun] {res['arch']} × {res['shape']} @ {res['mesh']}: "
        f"compile {res['t_compile_s']}s | mem/dev {gb:.2f} GiB | "
        f"flops/dev {res['per_device_flops']:.3e} | "
        f"compute {r['compute_s']*1e3:.2f}ms memory {r['memory_s']*1e3:.2f}ms "
        f"collective {r['collective_s']*1e3:.2f}ms → {r['dominant']}"
    )


if __name__ == "__main__":
    main()
