"""Every APSS dry-run cell must BUILD (abstract shapes + shardings) on a
mesh — catches config/spec regressions in seconds without the 512-device
compile (which lives in launch/dryrun.py)."""

import jax
import pytest

from repro.compat import make_mesh
from repro.configs import get_arch


@pytest.fixture(scope="module")
def small_mesh():
    # axis names match production; sizes divide every cell's shapes
    return make_mesh((2, 2), ("data", "model"))


ALL_CELLS = [("apss", s) for s in get_arch("apss").shapes]


@pytest.mark.parametrize("arch_name,shape_name", ALL_CELLS)
def test_cell_builds(arch_name, shape_name, small_mesh):
    arch = get_arch(arch_name)
    cell = arch.cell(shape_name)
    cfg = arch.make_config()
    build = cell.build(cfg, small_mesh)
    # args are abstract (no allocation), shardings present, fn callable
    assert callable(build.fn)
    leaves = jax.tree.leaves(build.args)
    assert leaves and all(isinstance(x, jax.ShapeDtypeStruct) for x in leaves)
    sh = jax.tree.leaves(build.in_shardings)
    assert sh and all(hasattr(s, "spec") for s in sh)
    assert "model_flops" in build.static_info or build.static_info
