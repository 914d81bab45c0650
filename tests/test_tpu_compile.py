"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Each test lowers and compiles one kernel wrapper for a described (not
attached) v5e chip, so what the chip's compiler refuses — unaligned blocks,
float iotas, more VMEM than a kernel may use — fails here, on the CPU,
with no chip. Nothing runs; results are pinned by the interpret-mode tests.

The shapes are those of the problems `TreeFamily.build_problem` builds:
(comparators N, leaves L, classes C, features F, test samples B).
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

SHAPES = {
    "seeds_tree": (7, 8, 3, 7, 63),
    "har_tree": (588, 589, 6, 561, 3090),
    "pendigits_forest4": (872, 876, 10, 16, 3298),
    "har_forest4": (1800, 1804, 6, 561, 3090),
    "pendigits_tree": (225, 226, 10, 16, 3298),
}
POP = 256           # chromosomes per fitness call in a pop_size=256 search
SEARCH_POP = {"pendigits_tree": 4096}   # the benchmark's large population
MLP_HIDDEN = 16     # printed-MLP default hidden width
MLP_POP = 64        # default population


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compilation cache off
    (a compile for a described chip can be written to it but never read)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            cc.reset_cache()


def _pad(x: int) -> int:
    return -(-x // 128) * 128


def _compile_has_kernel(fn, *args) -> None:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _sds(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _tree_operands(spec, s, *, with_sel: bool):
    """Padded static operands: `prepare_operands` (with the (F, N) selector)
    or `prepare_fitness_operands` (with the hoisted (B, N) gather)."""
    n, l, c, f, b = SHAPES[spec]
    n_p, l_p, c_p = _pad(n), _pad(l), _pad(c)
    head = s((_pad(f), n_p)) if with_sel else s((b, n_p))
    return (head, s((n_p, l_p)), s((1, l_p)), s((l_p, c_p)))


@pytest.mark.parametrize("spec", ["seeds_tree", "har_tree",
                                  "pendigits_forest4", "pendigits_tree"])
def test_fitness_kernel_compiles_default_block_l(one_chip, spec):
    n, _, _, _, b = SHAPES[spec]
    p = SEARCH_POP.get(spec, POP)
    s = functools.partial(_sds, one_chip)
    fit_ops = _tree_operands(spec, s, with_sel=False) + (s((1, b)),)
    _compile_has_kernel(
        lambda o, sc, t, v: ops.fitness_errors(o, sc, t, v, interpret=False),
        fit_ops, s((p, n)), s((p, n)), s((p,)))


@pytest.mark.parametrize("bucket", [8, 64, 1024])
def test_serving_tree_infer_compiles_har_forest(one_chip, bucket):
    """P = 1 serving at ClassifyServer's power-of-two buckets (the whole
    1,920-leaf axis runs out of VMEM at the small ones)."""
    n, _, _, f, _ = SHAPES["har_forest4"]
    s = functools.partial(_sds, one_chip)
    pt_ops = _tree_operands("har_forest4", s, with_sel=True)
    _compile_has_kernel(
        lambda x, o, sc, t, v: ops.classify(
            x, o, (sc, t, v), block_b=min(256, bucket), interpret=False),
        s((bucket, f), jnp.int32), pt_ops, s((1, n)), s((1, n)), s((1,)))


def test_tree_infer_predict_compiles_for_a_population(one_chip):
    n, _, _, f, b = SHAPES["har_tree"]
    s = functools.partial(_sds, one_chip)
    pt_ops = _tree_operands("har_tree", s, with_sel=True)
    p = 64
    _compile_has_kernel(
        lambda x, o, sc, t, v: ops.tree_infer_predict(x, o, sc, t, v,
                                                      interpret=False),
        s((b, f), jnp.int32), pt_ops, s((p, n)), s((p, n)), s((p,)))


@pytest.mark.parametrize("rows", [1024, 8192])
def test_domination_block_compiles_1024(one_chip, rows):
    """1,024 rows, and the 8,192-row pool of a pop-4,096 search."""
    s = functools.partial(_sds, one_chip)
    _compile_has_kernel(
        lambda a, b: ops.domination_block(a, b, interpret=False),
        s((rows, 2)), s((rows, 2)))


def test_qmatmul_compiles_at_mlp_route_shape(one_chip):
    """x8f (B, F) @ int8 weights (F, P*H): the printed-MLP kernel fitness's
    one launch per generation, at HAR widths."""
    _, _, _, f, b = SHAPES["har_tree"]
    cols = MLP_POP * MLP_HIDDEN
    s = functools.partial(_sds, one_chip)
    _compile_has_kernel(
        lambda x, w, sc: ops.qmatmul(x, w, sc, interpret=False),
        s((b, f)), s((f, cols), jnp.int8), s((cols,)))
