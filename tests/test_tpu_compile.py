"""Ahead-of-time TPU v5e compiles of the main-path kernels.

Interpret mode on the CPU runs a kernel's body as plain JAX, so it accepts
programs the chip's Mosaic compiler refuses (an unsupported primitive such
as ``cumsum``, a block shape off the (8, 128) tiling, a 3-D gather).  These
tests compile each kernel of the served path for a described — not attached
— v5e chip at the shapes ``chip_smoke.py`` drives (a 4096 x 4096 signal,
k = 16 trees, the built coreset's ~1.6k blocks) and check that the Pallas
kernel is in the compiled program.  The compensated (two-float) integral
images, which the chip runs by default, are also checked against the
device's memory, and compiled at the benchmark's 2400 x 2400 tile and its
600 x 2400 bands too.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N = M = 4096            # the smoke's signal
DELTA_ROWS = 64         # an appended band
REPLACE_TAIL = N // 2   # rows a replace at the smoke's mid-signal row patches
STREAM_ROWS = N + 64    # rows of the smoke's raster after its append
BLOCKS = 1588           # blocks of its (k=16, eps=0.2) coreset
K = T = 16              # leaves per tree, trees per batch
HIST_P, HIST_F, HIST_BINS = 6352, 2, 256   # forest fit on the coreset points
TILE = 2400             # a benchmark record: one MODIS tile, built whole
TILE_BAND = TILE // 4   # and as four row bands
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without the chip; keep these compiles out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_sat_moments_compiles(one_chip):
    from repro.kernels.sat2d.ops import _sat_moments_plain
    compiled = _sat_moments_plain.lower(
        _sds((N, M), one_chip), tile=256, interpret=False).compile()
    _assert_kernel(compiled)


def test_delta_sat_compiles(one_chip):
    from repro.kernels.sat2d.ops import _delta_plain
    compiled = _delta_plain.lower(
        _sds((3, M), one_chip), _sds((DELTA_ROWS, M), one_chip),
        tile=256, interpret=False).compile()
    _assert_kernel(compiled)


def test_sat_stack_compiles(one_chip):
    from repro.kernels.sat2d.ops import _stack_plain
    compiled = _stack_plain.lower(
        _sds((4, 3, 512, 512), one_chip), tile=256, interpret=False).compile()
    _assert_kernel(compiled)


def test_fitting_loss_compiles(one_chip):
    from repro.kernels.fitting_loss.kernel import fitting_loss_call
    blocks = _sds((BLOCKS, 4), one_chip)
    compiled = fitting_loss_call.lower(
        blocks, blocks, blocks, _sds((K, 4), one_chip), _sds((K,), one_chip),
        interpret=False).compile()
    _assert_kernel(compiled)


def test_fitting_loss_batched_compiles(one_chip):
    from repro.kernels.fitting_loss.kernel import fitting_loss_batched_call
    blocks = _sds((BLOCKS, 4), one_chip)
    compiled = fitting_loss_batched_call.lower(
        blocks, blocks, blocks, _sds((T, K, 4), one_chip),
        _sds((T, K), one_chip), interpret=False).compile()
    _assert_kernel(compiled)


def test_hist_split_fused_compiles(one_chip):
    from repro.kernels.histsplit.kernel import histograms_kernel_call
    compiled = histograms_kernel_call.lower(
        _sds((HIST_F, HIST_P), one_chip, jnp.int32),
        _sds((HIST_P, 3), one_chip), n_bins=HIST_BINS, variant="fused",
        interpret=False).compile()
    _assert_kernel(compiled)


def _fits(compiled):
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES // 2, used


def test_sat_moments_comp_compiles(one_chip):
    """The chip's integral-image path: two-float scans of a 4096^2 signal
    must fit a v5e's HBM with room for the served state."""
    from repro.kernels.sat2d.ops import sat_moments_comp
    y = _sds((N, M), one_chip)
    compiled = sat_moments_comp.lower(y, y, interpret=False).compile()
    _assert_kernel(compiled)
    _fits(compiled)


@pytest.mark.parametrize("rows", [TILE, TILE_BAND])
def test_sat_moments_comp_compiles_at_cell_shapes(one_chip, rows):
    """The benchmark's builds: the whole tile and each band read back the
    S1/S2 pairs alone, (2, n, m) each; the host makes the counts."""
    from repro.kernels.sat2d.ops import sat_moments_comp
    y = _sds((rows, TILE), one_chip)
    compiled = sat_moments_comp.lower(y, y, interpret=False).compile()
    _assert_kernel(compiled)
    _fits(compiled)
    assert [(o.shape, o.dtype) for o in compiled.out_info] == \
        [((2, rows, TILE), jnp.float32)] * 2


@pytest.mark.parametrize("rows", [DELTA_ROWS, REPLACE_TAIL])
def test_delta_sat_comp_compiles(one_chip, rows):
    from repro.kernels.sat2d.ops import delta_sat_moments_comp
    carry, tail = _sds((3, M), one_chip), _sds((rows, M), one_chip)
    compiled = delta_sat_moments_comp.lower(
        carry, carry, tail, tail, interpret=False).compile()
    _assert_kernel(compiled)
    _fits(compiled)


def test_sat_stack_comp_compiles(one_chip):
    """streaming_compress: one bucket's moment rasters at the full grid."""
    from repro.kernels.sat2d.ops import sat_stack_comp
    stk = _sds((1, 3, STREAM_ROWS, M), one_chip)
    compiled = sat_stack_comp.lower(stk, stk, interpret=False).compile()
    _assert_kernel(compiled)
    _fits(compiled)
