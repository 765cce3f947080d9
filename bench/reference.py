"""Plain numpy reference for what the placement service answers.

Imports nothing from `src/repro`, so no change to the program can move it.
From a configuration's `device` section it rebuilds the device geometry and
the conv-unit netlist (RapidLayout, arXiv:2002.06998, Fig. 1, SS III-C and
Table II, as the program models them), then

  * decodes a three-tier genotype (distribution, location, mapping) into
    logical block coordinates, step by step as the paper's decoder does,
    with the integer decisions taken in float32, the precision the
    configuration states;
  * evaluates paper Eq. 1 (squared weighted wirelength) and Eq. 2 (largest
    conv-unit bounding box) in float64, or in bfloat16 for the control;
  * checks legality from the coordinates alone: every chain on a column of
    its type, cascade members on consecutive sites, no site used twice, all
    inside the rectangle, every mapping a permutation;
  * gives the gap between the objectives the program reported for one
    genotype and those of its reference decode (`genotype_gap`).

What an algorithm's final state must satisfy beyond that (a population's
order, how its champion is chosen) is checked by the configuration's
algorithm module, `bench/algorithms/<algorithm>.py`.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

URAM, DSP, BRAM = 0, 1, 2
TYPES = (URAM, DSP, BRAM)
SITES_PER_CR = (16, 24, 24)        # sites per 60-row clock region (RAMB18)
ROWS_PER_CR = 60
CHAIN_LEN = (2, 9, 4)              # cascade chain length per type
CHAINS_PER_UNIT = (1, 2, 2)        # 2 URAM + 18 DSP + 8 RAMB18 per unit
SITE_STEP = (1, 1, 2)              # RAMB18 cascades step two rows (Eq. 5)
BLOCKS_PER_UNIT = 28
# blocks of one unit in logical order: (type, chain role, chain length)
ROLE_LAYOUT = ((URAM, 0, 2), (DSP, 0, 9), (DSP, 1, 9), (BRAM, 0, 4),
               (BRAM, 1, 4))
ALLOC_MARGIN = 2e-5                # relative slack of a float32 softmax
OBJECTIVE_GAP_LIMIT = 1e-3         # set from chip readings (PERF.md)


class Problem:
    """Device geometry and netlist of one configuration, as plain arrays."""

    def __init__(self, device: Dict):
        n = device["units_per_rect"]
        xs = _column_xs(device["n_uram_cols"], device["n_dsp_cols"],
                        device["n_bram_cols"], device["seed"])
        self.n_units = n
        self.col_x, self.cap_chains, self.parity = [], [], []
        for t in TYPES:
            sites = 2 * SITES_PER_CR[t]                # two clock regions
            x = xs[t]
            par = np.zeros(len(x), np.int64)
            if t == BRAM:                              # two parity sub-columns
                x = np.repeat(x, 2)
                sites //= 2
                par = np.tile(np.array([0, 1], np.int64), len(xs[t]))
            self.col_x.append(x.astype(np.float32))
            self.cap_chains.append(np.full(len(x), sites // CHAIN_LEN[t],
                                           np.int64))
            self.parity.append(par)
        self.n_chains = [n * CHAINS_PER_UNIT[t] for t in TYPES]
        self.pitch = [np.float32(ROWS_PER_CR / SITES_PER_CR[t])
                      for t in TYPES]
        self.src, self.dst, self.w = _nets(n)
        self.blk_type, self.blk_chain, self.blk_off = _blocks(n)
        base = np.cumsum([0] + [self.n_chains[t] * CHAIN_LEN[t]
                                for t in TYPES])
        self.flatpos = np.array(
            [base[t] + c * CHAIN_LEN[t] + o for t, c, o in
             zip(self.blk_type, self.blk_chain, self.blk_off)], np.int64)

    @property
    def n_blocks(self) -> int:
        return len(self.blk_type)

    @property
    def n_nets(self) -> int:
        return len(self.src)

    def sizes(self) -> Dict[str, Tuple[int, ...]]:
        """Genotype leaf lengths per tier, as the program must shape them."""
        return {"dist": tuple(len(x) for x in self.col_x),
                "loc": tuple(self.n_chains), "perm": tuple(self.n_chains)}


def _column_xs(n_uram: int, n_dsp: int, n_bram: int, seed: int,
               width: float = 680.0) -> Dict[int, np.ndarray]:
    """Seeded irregular interleave of hard-block columns (x in RPM units)."""
    rng = np.random.default_rng(seed)
    tags = [URAM] * n_uram + [DSP] * n_dsp + [BRAM] * n_bram
    idx = np.concatenate([(np.arange(k) + 0.5) / k
                          + rng.uniform(-.35, .35, k) / k
                          for k in (n_uram, n_dsp, n_bram)])
    order = np.argsort(idx, kind="stable")
    xs = np.cumsum(rng.uniform(6.0, 16.0, size=len(tags)))
    xs = xs / xs[-1] * width
    out: Dict[int, List[float]] = {t: [] for t in TYPES}
    for pos, col in enumerate(order):
        out[tags[col]].append(xs[pos])
    return {t: np.asarray(v, np.float64) for t, v in out.items()}


def _gid(unit: int, slot: int, off: int) -> int:
    return (unit * BLOCKS_PER_UNIT + sum(ln for _, _, ln in ROLE_LAYOUT[:slot])
            + off)


def _nets(n_units: int):
    """Routed nets of the conv-unit netlist: (src gid, dst gid, weight)."""
    nets: List[Tuple[int, int, float]] = []
    for k in range(n_units):
        u0, u1 = _gid(k, 0, 0), _gid(k, 0, 1)
        for bram, dsp in ((3, 1), (4, 2)):
            nets.append((u0, _gid(k, bram, 0), 4.0))        # URAM -> buffers
            nets.append((_gid(k, dsp, 8), u1, 4.0))         # accum -> URAM
            nets.append((u0, _gid(k, dsp, 0), 2.0))         # control fanout
            for j in range(4):                              # buffers -> DSPs
                nets.append((_gid(k, bram, j), _gid(k, dsp, 2 * j), 2.0))
                nets.append((_gid(k, bram, j), _gid(k, dsp, 2 * j + 1), 2.0))
            nets.append((_gid(k, bram, 3), _gid(k, dsp, 8), 2.0))
        if k + 1 < n_units:                                 # systolic chain
            nets.append((u1, _gid(k + 1, 0, 0), 2.0))
    a = np.array(nets)
    return a[:, 0].astype(np.int64), a[:, 1].astype(np.int64), a[:, 2]


def _blocks(n_units: int):
    """gid -> (type, logical chain, offset in chain)."""
    typ, chain, off = [], [], []
    for k in range(n_units):
        for t, role, ln in ROLE_LAYOUT:
            for o in range(ln):
                typ.append(t)
                chain.append(k * CHAINS_PER_UNIT[t] + role)
                off.append(o)
    return (np.array(typ, np.int64), np.array(chain, np.int64),
            np.array(off, np.int64))


# ------------------------------------------------------------------ decode

def _allocate(genes: np.ndarray, caps: np.ndarray, total: int
              ) -> Tuple[np.ndarray, bool]:
    """Chains per column: softmax share, floor, leftover by fractional
    priority.  Returns (counts, ambiguous): ambiguous when a float32
    rounding could flip a floor or the priority order, so the decode of
    this genotype is not decided by the configuration's precision."""
    g = np.asarray(genes, np.float32)
    e = np.exp(g - g.max())
    p = e / e.sum(dtype=np.float32)
    desired = (p * np.float32(total)).astype(np.float32)
    base = np.minimum(np.floor(desired), caps.astype(np.float32))
    base = base.astype(np.int64)
    room = caps - base
    prio = np.where(room > 0, desired - base.astype(np.float32),
                    np.float32(-1.0))
    order = np.argsort(-prio, kind="stable")
    counts = base + _give(order, room, total - base.sum())
    margin = ALLOC_MARGIN * np.maximum(1.0, np.abs(desired))
    frac = desired - np.floor(desired)
    ambiguous = bool(np.any(((frac < margin) | (1.0 - frac < margin))
                            & (desired < caps)))
    for k in range(len(order) - 1):
        a, b = order[k], order[k + 1]
        # equal genes give bitwise-equal priorities on any backend, and
        # both sides break the tie by column index
        if (room[a] > 0 and room[b] > 0 and g[a] != g[b]
                and prio[a] - prio[b] < max(margin[a], margin[b])):
            swapped = order.copy()
            swapped[k], swapped[k + 1] = b, a
            if not np.array_equal(base + _give(swapped, room,
                                               total - base.sum()), counts):
                ambiguous = True
    return counts, ambiguous


def _give(order: np.ndarray, room: np.ndarray, rem: int) -> np.ndarray:
    room_s = room[order]
    give_s = np.clip(rem - (np.cumsum(room_s) - room_s), 0, room_s)
    give = np.zeros_like(room)
    give[order] = give_s
    return give


def _decode_type(prob: Problem, t: int, dist, loc
                 ) -> Tuple[np.ndarray, np.ndarray, bool]:
    """One type's chains -> (x, y) [chains, chain length] and ambiguity."""
    n, ln = prob.n_chains[t], CHAIN_LEN[t]
    caps = prob.cap_chains[t]
    counts, ambiguous = _allocate(dist, caps, n)
    bounds = np.cumsum(counts)
    col = np.clip(np.searchsorted(bounds, np.arange(n), side="right"),
                  0, len(caps) - 1)
    locc = np.clip(np.asarray(loc, np.float32), np.float32(0.0),
                   np.float32(1.0 - 1e-6))
    key = col.astype(np.float32) * np.float32(2.0) + locc
    order = np.argsort(key, kind="stable")
    col_s, loc_s = col[order], locc[order]
    rank_s = np.arange(n) - (bounds - counts)[col_s]
    # slack sites spread by the location genes, monotone within a column
    slack = ((caps - counts) * ln)[col_s].astype(np.float32)
    off = np.minimum(np.floor(loc_s * (slack + np.float32(1.0))), slack)
    off = _segment_cummax(off.astype(np.int64), col_s)
    ystart = np.zeros(n, np.int64)
    ystart[order] = rank_s * ln + off
    site = ystart[:, None] + np.arange(ln)[None, :]
    row = site * SITE_STEP[t] + prob.parity[t][col][:, None]
    y = row.astype(np.float32) * prob.pitch[t]
    x = np.repeat(prob.col_x[t][col][:, None], ln, axis=1)
    return x, y, ambiguous


def _segment_cummax(v: np.ndarray, seg: np.ndarray) -> np.ndarray:
    out = v.copy()
    for i in range(1, len(v)):
        if seg[i] == seg[i - 1]:
            out[i] = max(out[i], out[i - 1])
    return out


def decode(prob: Problem, g: Dict) -> Tuple[np.ndarray, np.ndarray, bool]:
    """Genotype {"dist", "loc", "perm"} (one tuple of 3 arrays each) ->
    logical block coordinates (x, y) float32 [blocks], and whether the
    decode is ambiguous at float32 rounding."""
    xs, ys, amb = [], [], False
    for t in TYPES:
        x, y, a = _decode_type(prob, t, g["dist"][t], g["loc"][t])
        perm = np.asarray(g["perm"][t], np.int64)
        xs.append(x[perm].reshape(-1))
        ys.append(y[perm].reshape(-1))
        amb |= a
    return (np.concatenate(xs)[prob.flatpos],
            np.concatenate(ys)[prob.flatpos], amb)


# -------------------------------------------------------------- objectives

def objectives(prob: Problem, bx, by, dtype=np.float64) -> np.ndarray:
    """(wl^2, max bbox) of paper Eqs. 1-2, every operation in `dtype`."""
    bx, by = np.asarray(bx).astype(dtype), np.asarray(by).astype(dtype)
    w = prob.w.astype(dtype)
    dl = (np.abs(bx[prob.src] - bx[prob.dst])
          + np.abs(by[prob.src] - by[prob.dst])) * w
    wl2 = np.sum(dl * dl, dtype=dtype)
    ux = bx.reshape(prob.n_units, BLOCKS_PER_UNIT)
    uy = by.reshape(prob.n_units, BLOCKS_PER_UNIT)
    bbox = np.max((ux.max(1) - ux.min(1)) + (uy.max(1) - uy.min(1)))
    return np.array([wl2, bbox], np.float64)


def control_objectives(prob: Problem, bx, by) -> np.ndarray:
    """The reference one precision below the configuration's float32:
    Eqs. 1-2 computed in bfloat16 (the control that must fail)."""
    import ml_dtypes
    return objectives(prob, bx, by, ml_dtypes.bfloat16)


# ---------------------------------------------------------------- legality

def illegal(prob: Problem, g: Dict, bx: np.ndarray, by: np.ndarray
            ) -> List[str]:
    """Names of the constraints the placement breaks (empty when legal)."""
    bad = []
    for t in TYPES:
        n, ln = prob.n_chains[t], CHAIN_LEN[t]
        perm = np.asarray(g["perm"][t])
        if not np.array_equal(np.sort(perm), np.arange(n)):
            bad.append(f"perm_{t}")
        sel = prob.blk_type == t
        x = np.zeros((n, ln))
        y = np.zeros((n, ln))
        x[prob.blk_chain[sel], prob.blk_off[sel]] = bx[sel]
        y[prob.blk_chain[sel], prob.blk_off[sel]] = by[sel]
        row = y / prob.pitch[t]
        if not np.array_equal(row, np.round(row)):
            bad.append(f"row_{t}")
            continue
        row = row.astype(np.int64)
        if np.any(x != x[:, :1]):
            bad.append(f"same_column_{t}")
        if np.any(np.diff(row, axis=1) != SITE_STEP[t]):
            bad.append(f"cascade_{t}")
        par = row[:, 0] % SITE_STEP[t]
        hit = ((x[:, :1] == prob.col_x[t][None, :])
               & (par[:, None] == prob.parity[t][None, :]))
        if not np.all(hit.sum(1) == 1):
            bad.append(f"on_column_{t}")
            continue
        col = hit.argmax(1)
        site = (row - prob.parity[t][col][:, None]) // SITE_STEP[t]
        if np.any(site < 0) or np.any(
                site >= (prob.cap_chains[t][col] * ln)[:, None]):
            bad.append(f"region_{t}")
        occupied = col[:, None] * 10_000 + site
        if len(np.unique(occupied)) != occupied.size:
            bad.append(f"exclusive_{t}")
    return bad


# ------------------------------------------------------- one genotype

def genotype_gap(prob: Problem, g: Dict, reported, control: bool = False
                 ) -> Tuple[bool, Optional[float]]:
    """One genotype against the objectives the program reported for it:
    (illegal, gap).  `illegal` when its reference decode breaks a
    constraint; `gap` the widest relative gap between `reported` and the
    float64 Eqs. 1-2 of that decode (inf for NaN or inf objectives), or
    None when a float32 rounding could change the decode.  `control=True`
    puts the bfloat16 reference in the place of `reported`."""
    bx, by, ambiguous = decode(prob, g)
    bad = bool(illegal(prob, g, bx, by))
    if ambiguous:
        return bad, None
    want = objectives(prob, bx, by)
    got = control_objectives(prob, bx, by) if control else reported
    gap = float(np.max(np.abs(np.asarray(got, np.float64) - want)
                       / np.maximum(want, 1e-30)))
    if not np.isfinite(gap):            # NaN or inf objectives fail
        gap = np.inf
    return bad, gap
