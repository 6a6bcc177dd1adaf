"""Main-path kernels compile for a TPU v5e at real shapes.

Nothing runs: each case lowers a jitted function against shapes placed on
one chip of a *described* ``v5e:2x2`` topology and has the TPU compiler
build it, which refuses what the chip would refuse (unaligned tiles, too
much fast memory, a program that does not fit).  Cases: the replay's
float64 scan kernels at a 512-rank stage shape, the serial rendez-vous
level of the DeepSeek-V3 expert-parallel replay, exanest-lm-100m's
``decode_step`` at the serve shape, and the Pallas kernels that lower for
the TPU (``ssd_scan`` does not: Mosaic has no ``cumsum``, and its ``dt``
block breaks the (8, 128) tiling for a head block below the head count).

The topology is described inside a fixture, never at import, and the
persistent compilation cache is off around these tests (a compile for a
described chip cannot be read back without one).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.exanet import scan_engine
from repro.core.exanet.sim import scan_take_masks


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


#: the widest stages of the HPCG weak 512-rank, 64-fault-set replay:
#: (acquires, columns, max contention group)
SCAN_STAGES = {"maxplus": (72, 64, 4), "running_max": (44, 64, 2)}


@pytest.mark.parametrize("kernel", sorted(SCAN_STAGES))
def test_scan_kernel_compiles_in_float64(one_chip, kernel):
    k, cols, group = SCAN_STAGES[kernel]
    first = np.zeros(k, bool)
    first[::group] = True
    takes = scan_take_masks(first, group)
    shifts = tuple(s for s, _ in takes)
    masks = [jax.ShapeDtypeStruct(m.shape, jnp.bool_, sharding=one_chip)
             for _, m in takes]
    with jax.enable_x64(True):
        arr = jax.ShapeDtypeStruct((k, cols), jnp.float64,
                                   sharding=one_chip)
        if kernel == "maxplus":
            lowered = scan_engine._maxplus_kernel(shifts).lower(
                arr, arr, masks)
        else:
            lowered = scan_engine._running_max_kernel(shifts).lower(
                arr, masks)
        compiled = lowered.compile()
    outs = jax.tree_util.tree_leaves(compiled.out_info)
    assert outs and all(o.dtype == jnp.float64 and o.shape == (k, cols)
                        for o in outs)


#: the largest serial level of the DeepSeek-V3 expert-parallel decode
#: replay (sends, stages, rows, columns)
SERIAL_LEVEL = (9271, 10, 721, 64)


@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_serial_rdv_level_compiles(one_chip, dtype):
    k, n_stages, u, cols = SERIAL_LEVEL
    consts = {"rows": jax.ShapeDtypeStruct((k, n_stages), jnp.int32,
                                           sharding=one_chip),
              "valid": jax.ShapeDtypeStruct((k, n_stages), jnp.bool_,
                                            sharding=one_chip)}
    with jax.enable_x64(dtype == "float64"):
        x = jax.ShapeDtypeStruct((2 * k + u, cols), jnp.dtype(dtype),
                                 sharding=one_chip)
        compiled = scan_engine._rdv_serial_kernel(1.4, 2.4).lower(
            x, consts).compile()
    (out,) = jax.tree_util.tree_leaves(compiled.out_info)
    assert out.shape == (k + u, cols) and out.dtype == jnp.dtype(dtype)


def test_lm_decode_step_compiles(one_chip):
    from repro.configs import get
    from repro.models import build_model
    model = build_model(get("exanest-lm-100m"))
    params = _on(one_chip, jax.eval_shape(model.init,
                                          jax.random.PRNGKey(0)))
    cache = _on(one_chip, jax.eval_shape(lambda: model.init_cache(4, 128)))
    batch = _on(one_chip, {"token": jax.ShapeDtypeStruct((4,), jnp.int32),
                           "pos": jax.ShapeDtypeStruct((4,), jnp.int32)})
    compiled = jax.jit(model.decode_step).lower(params, cache,
                                                batch).compile()
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 16e9
    logits = jax.tree_util.tree_leaves(compiled.out_info)[0]
    assert logits.shape == (4, 1, 32000)


def _flash_decode(sd):
    from repro.kernels.flash_decode.kernel import flash_decode
    bf = jnp.bfloat16
    return flash_decode, (sd((4, 12, 64), bf), sd((4, 4096, 4, 64), bf),
                          sd((4, 4096, 4, 64), bf), sd((), jnp.int32))


def _matmul_tile(sd):
    from repro.kernels.matmul_tile.kernel import matmul_tile
    return matmul_tile, (sd((4096, 4096), jnp.bfloat16),
                         sd((4096, 4096), jnp.bfloat16))


def _combine(sd):
    from repro.kernels.allreduce_combine.kernel import combine
    return combine, (sd((8, 2 ** 20), jnp.float32),)


PALLAS = {"flash_decode": _flash_decode, "matmul_tile": _matmul_tile,
          "combine": _combine}


@pytest.mark.parametrize("name", sorted(PALLAS))
def test_pallas_kernel_compiles(one_chip, name):
    def sd(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = PALLAS[name](sd)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name
