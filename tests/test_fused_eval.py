"""Differential harness for the fused evaluation pipeline.

Four layers of defence, mirroring how the kernel can fail:

  1. interpret-vs-ref shape/dtype sweeps (`pallas_interpret`): the tiled
     Pallas body, executed on CPU, must match `ref.fused_eval_ref` across
     extents crossing every tile boundary (tests/_kernel_sweeps.py).
  2. padding-contract unit tests: the `kernels._padding` helpers (re-
     exported by `ops`) must produce padding that is *neutral under the
     fused reduction* -- planted worst-case values in the only cells the
     padding can reference must not leak into results.
  3. property tests (hypothesis when installed, deterministic fallback
     otherwise): permutation-invariance of domination rank, translation-
     invariance of bbox, exact quadratic scaling of wirelength^2 in the
     net weights.
  4. dispatch equivalence: on CPU `ops.fused_eval` (ref oracle) must be
     bitwise identical to the unfused two-op dispatch.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).parent))
from _hypothesis_compat import given, settings, st  # noqa: E402
from _kernel_sweeps import (DOM_SIZES, DTYPES, EVAL_SHAPES,  # noqa: E402
                            POP_SIZES, make_dom_case, make_eval_case, tol)

from repro.kernels import fused_eval as FE  # noqa: E402
from repro.kernels import ops, ref  # noqa: E402

# ------------------------------------------------- interpret-vs-ref sweeps


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("g,n,u,b", EVAL_SHAPES)
def test_fused_eval_shapes_match_ref(g, n, u, b):
    c = make_eval_case(5, g, n, u, b)
    got = FE.fused_eval_pallas(*c, interpret=True)
    want = ref.fused_eval_ref(*c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               **tol(jnp.float32))


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("p", POP_SIZES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_eval_pop_tiles_and_dtypes(p, dtype):
    c = make_eval_case(p, 96, 200, 37, 11, dtype=dtype, seed=p)
    got = FE.fused_eval_pallas(*c, interpret=True)
    want = ref.fused_eval_ref(*c)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol(dtype))


@pytest.mark.pallas_interpret
def test_fused_eval_batched_axes_flatten():
    """Leading (slots, islands) axes flatten into the population grid."""
    c = make_eval_case(12, 96, 50, 9, 7)
    cx = c.cx.reshape(2, 2, 3, -1)
    cy = c.cy.reshape(2, 2, 3, -1)
    got = FE.fused_eval_pallas(cx, cy, c.src, c.dst, c.w, c.uidx,
                               interpret=True)
    assert got.shape == (2, 2, 3, 2)
    flat = FE.fused_eval_pallas(*c, interpret=True)
    np.testing.assert_array_equal(np.asarray(got).reshape(12, 2),
                                  np.asarray(flat))


@pytest.mark.pallas_interpret
@pytest.mark.parametrize("p", DOM_SIZES)
def test_domination_counts_match_ref(p):
    objs = make_dom_case(p)
    dom, cnt = FE.domination_counts_pallas(objs, interpret=True)
    want = ref.domination_ref(objs)
    np.testing.assert_array_equal(np.asarray(dom.astype(bool)),
                                  np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(cnt), np.asarray(want).astype(np.int32).sum(axis=0))


# --------------------------------------------------- padding contracts


def test_pad_net_indices_weights_are_zero():
    src = jnp.arange(11, dtype=jnp.int32)
    dst = jnp.arange(11, dtype=jnp.int32)[::-1]
    w = jnp.ones(11)
    ps, pd, pw = ops.pad_net_indices(src, dst, w, 512)
    assert ps.shape == (512,)
    np.testing.assert_array_equal(np.asarray(pw[11:]), 0.0)
    # indices stay in range of any gid table (they pad with 0)
    assert int(jnp.max(ps)) <= 10 and int(jnp.min(ps)) >= 0


def test_pad_unit_index_rows_are_gid_zero():
    uidx = jnp.arange(5 * 7, dtype=jnp.int32).reshape(5, 7) + 3
    p = ops.pad_unit_index(uidx, 128, bb=8)
    assert p.shape == (128, 8)
    # padded blocks replicate each unit's last block (edge padding)
    np.testing.assert_array_equal(np.asarray(p[:5, 7]),
                                  np.asarray(uidx[:, -1]))
    # padded unit rows are all gid 0 -> degenerate unit, bbox exactly 0
    np.testing.assert_array_equal(np.asarray(p[5:]), 0)


def test_padded_nets_neutral_worst_case():
    """Plant the worst case the net padding can reference: gid 0 sits at
    an extreme coordinate.  Padded nets gather gid 0 with w == 0, so the
    fused result must equal the ref on the unpadded inputs."""
    c = make_eval_case(4, 96, 513, 9, 7)          # 513 nets: one over a tile
    cx = c.cx.at[:, 0].set(1e9)
    cy = c.cy.at[:, 0].set(-1e9)
    got = FE.fused_eval_pallas(cx, cy, c.src, c.dst, c.w, c.uidx,
                               interpret=True)
    want = ref.fused_eval_ref(cx, cy, c.src, c.dst, c.w, c.uidx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5)


def test_padded_units_neutral_worst_case():
    """129 units forces a padded unit tile whose rows gather gid 0; with
    gid 0 planted at an extreme coordinate the degenerate unit's bbox is
    still exactly 0 and must not move the max."""
    c = make_eval_case(4, 640, 40, 129, 5)
    cx = c.cx.at[:, 0].set(3.0e37)
    cy = c.cy.at[:, 0].set(-3.0e37)
    got = FE.fused_eval_pallas(cx, cy, c.src, c.dst, c.w, c.uidx,
                               interpret=True)
    want = ref.fused_eval_ref(cx, cy, c.src, c.dst, c.w, c.uidx)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_pad_unit_blocks_neutral_under_ref():
    """bbox layout: replicate-padding blocks and units never moves the
    min/max reduction."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    ux = jax.random.normal(k1, (3, 5, 13)) * 50      # [P, B, U] layout
    uy = jax.random.normal(k2, (3, 5, 13)) * 50
    px, py = ops.pad_unit_blocks(ux, uy, 8, 128)
    assert px.shape == (3, 8, 128)
    got = ref.maxbbox_ref(jnp.swapaxes(px, 1, 2), jnp.swapaxes(py, 1, 2))
    want = ref.maxbbox_ref(jnp.swapaxes(ux, 1, 2), jnp.swapaxes(uy, 1, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)


def test_pad_objs_inf_rows_dominate_nothing():
    objs = make_dom_case(9)
    padded = ops.pad_objs_inf(objs, 128)
    assert padded.shape == (128, 2)
    dom = np.asarray(ref.domination_ref(padded))
    # padded rows (>= 9) dominate nothing: their count contribution is 0
    assert not dom[9:, :].any()
    np.testing.assert_array_equal(
        dom[:9, :9], np.asarray(ref.domination_ref(objs)))


def test_pad_multiple_modes():
    a = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    z = ops.pad_multiple(a, 1, 4, mode="zero")
    e = ops.pad_multiple(a, 1, 4, mode="edge")
    np.testing.assert_array_equal(np.asarray(z[:, 2:]), 0.0)
    np.testing.assert_array_equal(np.asarray(e[:, 2:]),
                                  [[2.0, 2.0], [4.0, 4.0]])
    assert ops.pad_multiple(a, 0, 2).shape == (2, 2)   # already aligned


# ------------------------------------------------------ property tests


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       p=st.integers(min_value=2, max_value=40))
def test_domination_rank_permutation_invariant(seed, p):
    """Relabeling candidates permutes their Pareto front indices."""
    from repro.core.nsga2 import nondominated_rank
    objs = make_dom_case(p, seed=seed)
    perm = jax.random.permutation(jax.random.PRNGKey(seed + 1), p)
    r = np.asarray(nondominated_rank(objs, fused=True))
    rp = np.asarray(nondominated_rank(objs[perm], fused=True))
    np.testing.assert_array_equal(r[np.asarray(perm)], rp)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       shift=st.integers(min_value=-500, max_value=500))
def test_bbox_translation_invariant(seed, shift):
    """Translating every block moves no bbox width/height."""
    c = make_eval_case(3, 96, 20, 9, 7, seed=seed)
    base = FE.fused_eval_pallas(*c, interpret=True)
    moved = FE.fused_eval_pallas(c.cx + shift, c.cy - shift, c.src, c.dst,
                                 c.w, c.uidx, interpret=True)
    np.testing.assert_allclose(np.asarray(moved[..., 1]),
                               np.asarray(base[..., 1]), rtol=1e-5,
                               atol=1e-3)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10_000),
       scale=st.integers(min_value=1, max_value=8))
def test_wirelength_quadratic_in_weights(seed, scale):
    """wl2(s * w) == s^2 * wl2(w): Eq. 1 is quadratic in the net weights,
    so scaling weights up can never decrease it (monotonicity)."""
    c = make_eval_case(3, 96, 200, 9, 7, seed=seed)
    base = FE.fused_eval_pallas(*c, interpret=True)[..., 0]
    scaled = FE.fused_eval_pallas(c.cx, c.cy, c.src, c.dst,
                                  c.w * float(scale), c.uidx,
                                  interpret=True)[..., 0]
    np.testing.assert_allclose(np.asarray(scaled),
                               float(scale) ** 2 * np.asarray(base),
                               rtol=1e-4)
    assert (np.asarray(scaled) >= np.asarray(base) - 1e-6).all()


# --------------------------------------------------- dispatch equivalence


def test_ops_fused_eval_bitwise_matches_unfused_dispatch():
    """On the CPU ref path, the fused dispatch is composed from the same
    oracles as the two-op dispatch -- bitwise identical."""
    c = make_eval_case(6, 96, 200, 37, 11)
    fused = ops.fused_eval(*c)
    wl = ops.wirelength2(c.cx[:, c.src], c.cy[:, c.src],
                         c.cx[:, c.dst], c.cy[:, c.dst], c.w)
    bb = ops.maxbbox(c.cx[:, c.uidx], c.cy[:, c.uidx])
    np.testing.assert_array_equal(np.asarray(fused[..., 0]), np.asarray(wl))
    np.testing.assert_array_equal(np.asarray(fused[..., 1]), np.asarray(bb))


def test_ops_fused_domination_counts_matches_matrix():
    objs = make_dom_case(50, seed=3)
    dom, cnt = ops.fused_domination_counts(objs)
    want = ops.domination_matrix(objs)
    np.testing.assert_array_equal(np.asarray(dom), np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(cnt), np.asarray(want).astype(np.int32).sum(axis=0))
