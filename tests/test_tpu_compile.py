"""Compile the main-path kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with jax, and it compiles for a chip that is
described (`topologies.get_topology_desc`) rather than attached.  These
tests lower and compile every evaluation kernel at the paper's VU11P
widths (population 64, 2,240 blocks, 1,999 nets, 80 units) and assert the
compiled text holds the Mosaic kernel (`tpu_custom_call`): what the chip's
compiler refuses -- unaligned blocks, in-kernel gathers, too much VMEM --
fails here at no chip time.  Nothing runs, so nothing here checks values.

The topology is described inside a module fixture, never while a module is
imported: only one process may load the TPU library at a time, and every
test worker imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bbox, domination, fused_eval, wirelength

P, G, N, U, B = 64, 2240, 1999, 80, 28      # VU11P rectangle widths


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_wirelength_compiles(chip):
    args = [_spec(chip, (P, N)) for _ in range(5)]
    assert "tpu_custom_call" in _compiled_text(
        wirelength.wirelength2_pallas, *args)


def test_maxbbox_compiles(chip):
    args = [_spec(chip, (P, U, B)) for _ in range(2)]
    assert "tpu_custom_call" in _compiled_text(bbox.maxbbox_pallas, *args)


def test_domination_compiles(chip):
    assert "tpu_custom_call" in _compiled_text(
        domination.domination_pallas, _spec(chip, (P, 2)))


@pytest.mark.parametrize("slots", [0, 8], ids=["pop", "vmap_slots"])
def test_domination_counts_compiles(chip, slots):
    """Also under the service's slot vmap, which turns every rank-1 block
    into a (squeezed, n) block that the lowering refuses."""
    fn = fused_eval.domination_counts_pallas
    shape = (P, 2)
    if slots:
        fn, shape = jax.vmap(fn), (slots, P, 2)
    assert "tpu_custom_call" in _compiled_text(fn, _spec(chip, shape))


@pytest.mark.parametrize("batch", [(P,), (8, P)], ids=["pop", "slots_pop"])
def test_fused_eval_compiles(chip, batch):
    args = (_spec(chip, (*batch, G)), _spec(chip, (*batch, G)),
            _spec(chip, (N,), jnp.int32), _spec(chip, (N,), jnp.int32),
            _spec(chip, (N,)), _spec(chip, (U, B), jnp.int32))
    assert "tpu_custom_call" in _compiled_text(
        fused_eval.fused_eval_pallas, *args)


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
def test_evaluate_population_compiles(chip, vu11p_problem, monkeypatch,
                                      fused):
    """The whole decode + objectives program on the `ops` TPU branch (the
    backend here is the CPU, so the test steers `ops` itself)."""
    from repro.core import genotype as Gt
    from repro.core import objectives as O
    from repro.kernels import ops

    g = jax.eval_shape(lambda k: Gt.random_genotype(k, vu11p_problem),
                       jax.random.PRNGKey(0))
    pop = jax.tree.map(lambda a: _spec(chip, (P, *a.shape), a.dtype), g)
    # traces cached on either branch must not leak across the patch
    jax.clear_caches()
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    try:
        text = O.evaluate_population.lower(
            vu11p_problem, pop, fused).compile().as_text()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "tpu_custom_call" in text
