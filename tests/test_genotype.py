"""Genotype decode legality + encodings, incl. hypothesis property tests."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import genotype as G
from repro.core import objectives as O
from repro.fpga import device, netlist

PROB = netlist.make_problem(device.get_device("xcvu_test"))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_random_genotype_always_decodes_legal(seed):
    """Every genotype decodes to a legal placement -- the paper's central
    genotype-design claim (cascade constraints encoded, no legalization)."""
    g = G.random_genotype(jax.random.PRNGKey(seed), PROB)
    O.assert_valid(PROB, g)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_flat_encoding_always_decodes_legal(seed):
    z = jax.random.normal(jax.random.PRNGKey(seed),
                          (PROB.continuous_dim,)) * 2.0
    O.assert_valid(PROB, G.from_flat(PROB, z))


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), total=st.integers(1, 40))
def test_allocation_exact_and_capped(seed, total):
    key = jax.random.PRNGKey(seed)
    caps = jnp.asarray([3, 7, 1, 9, 5, 8, 4, 3], jnp.int32)
    genes = jax.random.normal(key, (8,)) * 3.0
    counts = G.allocate_counts(genes, caps, total)
    assert int(counts.sum()) == total
    assert bool((counts <= caps).all()) and bool((counts >= 0).all())


def test_allocation_follows_genes():
    caps = jnp.full((4,), 100, jnp.int32)
    genes = jnp.asarray([5.0, 0.0, 0.0, 0.0])
    counts = G.allocate_counts(genes, caps, 40)
    assert int(counts[0]) > 30  # dominant gene takes the bulk


def test_flat_roundtrip_perm_exact():
    g = G.random_genotype(jax.random.PRNGKey(3), PROB)
    g2 = G.from_flat(PROB, G.to_flat(PROB, g))
    for t in range(3):
        np.testing.assert_array_equal(np.asarray(g2["perm"][t]),
                                      np.asarray(g["perm"][t]))
        np.testing.assert_allclose(np.asarray(g2["loc"][t]),
                                   np.asarray(g["loc"][t]), atol=1e-5)


def test_reduced_decode_matches_packed_layout():
    g = G.random_genotype(jax.random.PRNGKey(1), PROB)
    bx, by = G.decode_reduced(PROB, g["perm"])
    assert bx.shape == (PROB.n_blocks,)
    assert not bool(jnp.isnan(bx).any() | jnp.isnan(by).any())


def test_mapping_changes_objectives_not_legality():
    """Permuting the mapping must change wirelength (different unit
    groupings) but never legality -- the mapping tier only relabels."""
    key = jax.random.PRNGKey(0)
    g = G.random_genotype(key, PROB)
    o1 = O.evaluate(PROB, g)
    g2 = dict(g)
    g2["perm"] = tuple(jnp.roll(p, 1) for p in g["perm"])
    o2 = O.evaluate(PROB, g2)
    O.assert_valid(PROB, g2)
    assert not np.allclose(np.asarray(o1), np.asarray(o2))


def test_distribution_tier_controls_columns():
    """Cranking one distribution gene concentrates chains in that column."""
    g = G.random_genotype(jax.random.PRNGKey(0), PROB)
    dist = list(g["dist"])
    dist[1] = jnp.zeros_like(dist[1]).at[0].set(10.0)  # DSP column 0
    g2 = {**g, "dist": tuple(dist)}
    bx, _ = G.decode(PROB, g2)
    dsp_x = PROB.geom[1].col_x[0]
    dsp_mask = PROB.blk_type == 1
    frac = np.mean(np.abs(np.asarray(bx)[dsp_mask] - dsp_x) < 1e-4)
    O.assert_valid(PROB, g2)
    assert frac > 0.3  # capacity-capped, but clearly concentrated


# ------------------------------------------- decode without column gathers

def _decode_type_searchsorted(geom, dist, loc):
    """The column-axis decode as it was written with `searchsorted` and
    per-column gathers: the reference the dense comparison must match."""
    N, L = geom.n_chains, geom.chain_len
    caps = jnp.asarray(geom.col_cap_chains)
    counts = G.allocate_counts(dist, caps, N)
    bounds = jnp.cumsum(counts)
    col = jnp.searchsorted(bounds, jnp.arange(N), side="right")
    col = jnp.clip(col.astype(jnp.int32), 0, geom.n_cols - 1)
    locc = jnp.clip(loc, 0.0, 1.0 - 1e-6)
    order = jnp.argsort(col.astype(jnp.float32) * 2.0 + locc)
    col_s = col[order]
    loc_s = locc[order]
    rank_s = jnp.arange(N) - (bounds - counts)[col_s]
    slack_sites = ((caps - counts) * L)[col_s].astype(jnp.float32)
    off = jnp.minimum(jnp.floor(loc_s * (slack_sites + 1.0)), slack_sites)
    off = G._seg_cummax(off, col_s)
    ystart_s = rank_s * L + off.astype(jnp.int32)
    ystart = jnp.zeros(N, jnp.int32).at[order].set(ystart_s)
    site = ystart[:, None] + jnp.arange(L)[None, :]
    parity = jnp.asarray(geom.col_parity)[col][:, None]
    y = (site * geom.site_step + parity).astype(jnp.float32) * geom.row_pitch
    x = jnp.asarray(geom.col_x)[col][:, None] * jnp.ones((1, L), jnp.float32)
    return x, y


def _decode_searchsorted(problem, g):
    xs, ys = [], []
    for t in G.TYPES:
        x, y = _decode_type_searchsorted(problem.geom[t], g["dist"][t],
                                         g["loc"][t])
        xs.append(x[g["perm"][t]].reshape(-1))
        ys.append(y[g["perm"][t]].reshape(-1))
    pos = jnp.asarray(problem.blk_flatpos)
    return jnp.concatenate(xs)[pos], jnp.concatenate(ys)[pos]


def _edge_case_batch(problem, n=64):
    """`n` genotypes: random ones, with rows overwritten by distributions
    that fill one column (middle, first, last) to capacity, one that leaves
    the first and last columns empty (where the others hold every chain),
    locations at 0 and at 1 - 1e-6, and `reduced_to_full`'s genotype."""
    keys = jax.random.split(jax.random.PRNGKey(14), n)
    batch = jax.jit(jax.vmap(lambda k: G.random_genotype(k, problem)))(keys)
    dist = [np.array(d) for d in batch["dist"]]
    loc = [np.array(v) for v in batch["loc"]]
    for t in G.TYPES:
        c = problem.geom[t].n_cols
        dist[t][0] = 0.0
        dist[t][0, c // 2] = 30.0                 # one column at capacity
        dist[t][1] = 0.0
        dist[t][1, [0, c - 1]] = -30.0            # first and last empty
        dist[t][6] = 0.0
        dist[t][6, 0] = 30.0
        dist[t][7] = 0.0
        dist[t][7, c - 1] = 30.0
        loc[t][2] = 0.0
        loc[t][3] = 1.0 - 1e-6
        loc[t][4, ::2] = 0.0                      # both ends in one genotype
        loc[t][4, 1::2] = 1.0 - 1e-6
    full = G.reduced_to_full(problem, tuple(p[5] for p in batch["perm"]))
    for t in G.TYPES:
        dist[t][5] = np.asarray(full["dist"][t])
        loc[t][5] = np.asarray(full["loc"][t])
    return {"dist": tuple(jnp.asarray(d) for d in dist),
            "loc": tuple(jnp.asarray(v) for v in loc),
            "perm": batch["perm"]}


@pytest.mark.parametrize("name", ["xcvu_test", "xcvu11p", "xcvu3p"])
def test_dense_column_decode_is_bit_identical_to_searchsorted(name):
    problem = netlist.make_problem(device.get_device(name))
    batch = _edge_case_batch(problem)
    new = jax.vmap(lambda g: G.decode(problem, g))(batch)
    old = jax.jit(jax.vmap(lambda g: _decode_searchsorted(problem, g)))(batch)
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_vmapped_decode_lowers_without_a_loop(vu11p_problem):
    """`searchsorted`'s default method is a `fori_loop` of gathers; the
    dense comparison over the columns leaves the decode no `while`."""
    batch = _edge_case_batch(vu11p_problem, n=8)
    hlo = jax.jit(jax.vmap(lambda g: G.decode(vu11p_problem, g))).lower(
        batch).as_text(dialect="hlo")
    assert not re.search(r"[\s)]while\(", hlo)
