"""The numpy reference against the program, at `xcvu_test` size on the CPU.

The reference imports nothing from `src/repro`; these tests tie it to the
program: same geometry and netlist, the same decode bit for bit, Eqs. 1-2
within float32 rounding.  The NSGA-II selection rules are tied to the
program in `test_bench_nsga2.py`.
"""
import json
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import reference as R  # noqa: E402
from repro.core import genotype as G  # noqa: E402
from repro.core import objectives as O  # noqa: E402
from repro.fpga import device, netlist  # noqa: E402

TEST_DEVICE = {"name": "xcvu_test", "units_per_rect": 6, "n_uram_cols": 2,
               "n_dsp_cols": 4, "n_bram_cols": 2, "seed": 7}


@pytest.fixture(scope="module")
def pair():
    prog = netlist.make_problem(device.get_device("xcvu_test"))
    return prog, R.Problem(TEST_DEVICE)


def genotypes(prog, n, seed=0):
    for k in range(n):
        yield G.random_genotype(jax.random.PRNGKey(seed * 1000 + k), prog)


def host(g):
    return jax.tree.map(np.asarray, g)


@pytest.mark.parametrize("config", ["vu11p_nsga2", "vu3p_nsga2", None])
def test_geometry_and_netlist_match_the_program(config):
    if config is None:
        spec, name = TEST_DEVICE, "xcvu_test"
    else:
        spec = json.loads((ROOT / "bench" / "configs"
                           / f"{config}.json").read_text())["device"]
        name = spec["name"]
    prog = netlist.make_problem(device.get_device(name))
    ref = R.Problem(spec)
    for t in R.TYPES:
        assert np.array_equal(ref.col_x[t], prog.geom[t].col_x)
        assert np.array_equal(ref.cap_chains[t], prog.geom[t].col_cap_chains)
        assert np.array_equal(ref.parity[t], prog.geom[t].col_parity)
        assert ref.n_chains[t] == prog.geom[t].n_chains
        assert ref.pitch[t] == np.float32(prog.geom[t].row_pitch)
    assert np.array_equal(ref.src, prog.net_src)
    assert np.array_equal(ref.dst, prog.net_dst)
    assert np.array_equal(ref.w, prog.net_w)
    assert np.array_equal(ref.flatpos, prog.blk_flatpos)


def test_decode_objectives_and_legality_match(pair):
    prog, ref = pair
    for g in genotypes(prog, 12):
        bx, by = G.decode(prog, g)
        rx, ry, ambiguous = R.decode(ref, host(g))
        assert not ambiguous
        assert np.array_equal(np.asarray(bx), rx)
        assert np.array_equal(np.asarray(by), ry)
        assert R.illegal(ref, host(g), rx, ry) == []
        got = np.asarray(O.evaluate(prog, g), np.float64)
        want = R.objectives(ref, rx, ry)
        assert np.max(np.abs(got - want) / want) < R.OBJECTIVE_GAP_LIMIT


def test_illegal_catches_a_broken_mapping(pair):
    prog, ref = pair
    g = host(next(genotypes(prog, 1, seed=3)))
    perm = np.array(g["perm"][R.DSP])
    perm[1] = perm[0]                      # two roles on one chain
    g["perm"] = (g["perm"][0], perm, g["perm"][2])
    rx, ry, _ = R.decode(ref, g)
    bad = R.illegal(ref, g, rx, ry)
    assert f"perm_{R.DSP}" in bad and f"exclusive_{R.DSP}" in bad


def test_control_misses_the_limit(pair):
    prog, ref = pair
    gaps = []
    for g in genotypes(prog, 6, seed=5):
        rx, ry, _ = R.decode(ref, host(g))
        want = R.objectives(ref, rx, ry)
        got = R.control_objectives(ref, rx, ry)
        gaps.append(np.max(np.abs(got - want) / want))
    assert min(gaps) > 10 * R.OBJECTIVE_GAP_LIMIT


# RAMB18 distribution genes of a member the chip decoded differently (VU11P,
# open mix, seed 4200000099): columns 9 and 15 hold leftover priorities
# 0.0753880 and 0.0753803, which the chip's float32 softmax ordered the
# other way, so one chain sat one column over and objective_gap read 9.6e-4.
CHIP_REORDERED_RAMB18 = [
    0.2463584542274475, -0.26119422912597656, -0.6455768346786499,
    -0.8136004209518433, 0.45956793427467346, -0.673141360282898,
    0.10345365107059479, 0.05168956518173218, -0.12318073958158493,
    -1.1867010593414307, -0.1559409201145172, -0.21802125871181488,
    -1.0803966522216797, -0.5050932168960571, 0.7270999550819397,
    -0.2924477458000183, -0.48767274618148804, -0.4362034797668457,
    -0.11773577332496643, -0.5724111795425415, -0.478874146938324,
    0.05204476788640022, -0.31052902340888977, -0.07742664217948914,
    -0.3014841079711914, -1.0837390422821045, 0.3272496461868286,
    0.13168036937713623]


@pytest.mark.parametrize("margin,flagged", [(1e-6, False),
                                            (R.ALLOC_MARGIN, True)])
def test_alloc_margin_covers_a_pair_the_chip_ordered_the_other_way(
        margin, flagged, monkeypatch):
    cfg = json.loads((ROOT / "bench" / "configs" / "vu11p_nsga2.json")
                     .read_text())
    prob = R.Problem(cfg["device"])
    genes = np.asarray(CHIP_REORDERED_RAMB18, np.float32)
    monkeypatch.setattr(R, "ALLOC_MARGIN", margin)
    counts, ambiguous = R._allocate(genes, prob.cap_chains[R.BRAM],
                                    prob.n_chains[R.BRAM])
    assert counts[[9, 15]].tolist() == [3, 5]     # the chip made [2, 6]
    assert ambiguous is flagged
