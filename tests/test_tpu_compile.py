"""The Pallas kernels of the detection path compile for a TPU v5e.

Nothing runs: each kernel is lowered with `interpret=False` and compiled
by the TPU compiler for a described (not attached) v5e chip, at the
sizes the detector feeds it -- 1080p and UHD dense slabs, 256-window
batches, the scoring matmuls of a 1080p block grid. This catches what
the interpreter cannot: ops Mosaic does not lower, misaligned blocks,
and blocks over the scoped-VMEM limit. Each compiled program must hold
the kernel as a `tpu_custom_call`.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library at a time, and a test run with
several workers imports every test file in each of them.
"""
import os

import pytest

CELL = 8
BUCKET = 32


def _dense_gray(h: int, w: int):
    """The dense gray the detector hands the HOG kernels for an (h, w)
    frame: padded to its shape bucket, trimmed to whole cells + border."""
    ph, pw = -(-h // BUCKET) * BUCKET, -(-w // BUCKET) * BUCKET
    return (1, (ph - 2) // CELL * CELL + 2, (pw - 2) // CELL * CELL + 2)


SIZES = {"1080p": _dense_gray(1080, 1920), "uhd": _dense_gray(2160, 3840)}
BLOCK_ROWS_1080P = 134 * 238            # block positions of a 1080p grid


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, one_chip, *shapes) -> str:
    import jax
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("mode", ["sector", "fixed"])
def test_dense_fused_hog_compiles(one_chip, size, mode):
    import jax.numpy as jnp
    from repro.kernels.fused_hog import dense_fused_hog
    text = _compiled_text(
        lambda g: dense_fused_hog(g, mode=mode, interpret=False),
        one_chip, (SIZES[size], jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("size", sorted(SIZES))
def test_dense_grad_hist_and_block_norm_compile(one_chip, size):
    import jax.numpy as jnp
    from repro.kernels.dense_block_norm import dense_block_norm
    from repro.kernels.dense_grad_hist import dense_grad_hist
    text = _compiled_text(
        lambda g: dense_block_norm(dense_grad_hist(g, interpret=False),
                                   interpret=False),
        one_chip, (SIZES[size], jnp.float32))
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("mode", ["sector", "fixed"])
def test_window_fused_hog_compiles(one_chip, mode):
    import jax.numpy as jnp
    from repro.kernels.fused_hog import fused_hog
    text = _compiled_text(lambda g: fused_hog(g, mode=mode, interpret=False),
                          one_chip, ((256, 130, 66), jnp.float32))
    assert "tpu_custom_call" in text


def test_cell_hist_compiles(one_chip):
    import jax.numpy as jnp
    from repro.kernels.cell_hist import cell_hist
    text = _compiled_text(lambda m, b: cell_hist(m, b, interpret=False),
                          one_chip, ((256, 128, 64), jnp.float32),
                          ((256, 128, 64), jnp.int32))
    assert "tpu_custom_call" in text


def test_score_matmul_bf16_compiles(one_chip):
    import jax.numpy as jnp
    from repro.kernels.svm_matmul import score_matmul
    text = _compiled_text(lambda x, w: score_matmul(x, w, interpret=False),
                          one_chip, ((BLOCK_ROWS_1080P, 36), jnp.bfloat16),
                          ((36, 105), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_score_matmul_int8_compiles(one_chip):
    import jax.numpy as jnp
    from repro.kernels.svm_matmul import score_matmul_int8
    text = _compiled_text(
        lambda q, w: score_matmul_int8(q, w, interpret=False),
        one_chip, ((BLOCK_ROWS_1080P, 36), jnp.int8), ((36, 105), jnp.int8))
    assert "tpu_custom_call" in text
