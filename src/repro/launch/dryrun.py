import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, extract memory/cost/collective numbers for the roofline analysis.

MUST be the first import in its process: the two lines above force 512
placeholder host devices BEFORE jax locks the device count.  Never set that
flag globally -- smoke tests and benches see the real single CPU device.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
        [--out experiments/dryrun]

Per cell it writes <out>/<arch>__<shape>__<mesh>.json with:
    memory_analysis (per-device bytes), cost_analysis (flops/bytes),
    collective bytes by kind (HLO parse, loop trip counts included),
    the rules used, timing, and the roofline terms.
"""
import argparse   # noqa: E402
import dataclasses  # noqa: E402
import json       # noqa: E402
import time       # noqa: E402
import traceback  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

import jax        # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import base as cbase  # noqa: E402
from repro.configs.base import SHAPES, input_specs, shape_applicable  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.sharding import costmodel as cm  # noqa: E402
from repro.sharding import hloparse, logical  # noqa: E402
from repro.train import optimizer as opt  # noqa: E402
from repro.train.train_step import make_train_step  # noqa: E402

# v5e constants (assignment)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9


def _sds_with_sharding(tree, axes_tree, mesh, rules, zero1: bool = False):
    """Attach NamedShardings to a ShapeDtypeStruct tree via logical axes.

    zero1=True additionally shards the first still-replicated dim over the
    batch axes (ZeRO-1 optimizer-state partitioning): GSPMD then materialises
    the reduce-scatter/all-gather pair around the update automatically.
    """

    def one(sds, axes):
        spec = logical.spec_for(axes, sds.shape, mesh, rules)
        if zero1:
            parts = list(spec)
            batch_ax = rules.get("batch") or ()
            batch_ax = ((batch_ax,) if isinstance(batch_ax, str)
                        else tuple(batch_ax))
            used = {a for p in parts if p
                    for a in ((p,) if isinstance(p, str) else p)}
            free = tuple(a for a in batch_ax
                         if a in mesh.shape and a not in used)
            if free:
                size = 1
                for a in free:
                    size *= mesh.shape[a]
                for i, p in enumerate(parts):
                    if p is None and sds.shape[i] % size == 0 \
                            and sds.shape[i] >= size:
                        parts[i] = free if len(free) > 1 else free[0]
                        break
                spec = jax.sharding.PartitionSpec(*parts)
        return jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=jax.sharding.NamedSharding(mesh, spec))

    return jax.tree.map(one, tree, axes_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _batch_axes_tree(batch_sds: Dict[str, jax.ShapeDtypeStruct]):
    out = {}
    for k, v in batch_sds.items():
        if k == "frontend_embeds":
            out[k] = ("batch", None, None)
        elif v.ndim == 2:
            out[k] = ("batch", None)
        else:
            out[k] = ("batch",)
    return out


def _cache_axes(cfg: T.ArchConfig, caches_sds):
    """Logical axes for the stacked cache tree (leading periods dim)."""
    def axes_for(leaf):
        nd = leaf.ndim
        if nd == 5:                  # [periods, B, Hkv, T, dh] attention
            return (None, "batch", None, "kv_seq", None)
        if nd == 4:                  # mamba h [periods,B,di,ds] / rwkv S...
            return (None, "batch", "ssm_inner", None)
        if nd == 3:
            return (None, "batch", None)
        if nd == 2:
            return (None, "batch")
        return (None,) * nd

    return jax.tree.map(axes_for, caches_sds,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _rwkv_cache_axes(leaf):
    return None


def build_lowerable(cfg: T.ArchConfig, shape_name: str, mesh, rules,
                    dtype=jnp.bfloat16):
    """Returns (fn, example_args_SDS, donate) for the cell's step."""
    ss = SHAPES[shape_name]
    paxes = T.param_axes(cfg)
    params_sds = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0), dtype))
    # FSDP: when TP-sharded weights still exceed ~4 GiB/device, shard the
    # remaining replicated dim over the batch axes (GSPMD all-gathers per
    # layer inside the scan -- standard FSDP semantics)
    tp = mesh.shape.get("model", 1)
    fsdp = (ss.kind == "train"
            and cfg.param_count() * 2 / tp > 4e9)
    if os.environ.get("REPRO_FSDP_PARAMS") == "1":
        fsdp = True        # SSPerf lever: weight-gathered decode/prefill
    params_sds = _sds_with_sharding(params_sds, paxes, mesh, rules,
                                    zero1=fsdp)

    if ss.kind == "train":
        ocfg = opt.OptConfig()
        opt_sds = jax.eval_shape(opt.init, params_sds)
        opt_axes = {
            "master": paxes, "m": paxes, "v": paxes, "step": (),
        }
        # ZeRO-1: fp32 master/m/v shard over the batch axes on top of TP
        opt_sds = _sds_with_sharding(opt_sds, opt_axes, mesh, rules,
                                     zero1=True)
        batch_sds = input_specs(cfg, shape_name)
        batch_sds = _sds_with_sharding(batch_sds, _batch_axes_tree(batch_sds),
                                       mesh, rules)
        n_micro = _auto_microbatch(cfg, ss, mesh, rules)
        step = make_train_step(cfg, ocfg, n_micro)
        return step, (params_sds, opt_sds, batch_sds), (0, 1), n_micro

    if ss.kind == "prefill":
        batch_sds = input_specs(cfg, shape_name)
        batch_sds = _sds_with_sharding(batch_sds, _batch_axes_tree(batch_sds),
                                       mesh, rules)
        max_len = ss.seq_len + cfg.n_frontend_tokens + 128

        def prefill_step(params, batch):
            return T.prefill(params, cfg, batch["tokens"], max_len,
                             batch.get("frontend_embeds"))

        return prefill_step, (params_sds, batch_sds), (), 1

    # decode
    b = ss.global_batch
    max_len = ss.seq_len
    caches_sds = jax.eval_shape(
        lambda: T.init_caches(cfg, b, max_len, dtype))
    caches_sds = _sds_with_sharding(
        caches_sds, _cache_axes(cfg, caches_sds), mesh, rules)
    io_sds = input_specs(cfg, shape_name)
    io_sds = _sds_with_sharding(
        io_sds, {"token": ("batch",), "cache_len": ("batch",)}, mesh, rules)

    def serve_step(params, token, caches, cache_len):
        return T.decode_step(params, cfg, token, caches, cache_len)

    return (serve_step,
            (params_sds, io_sds["token"], caches_sds, io_sds["cache_len"]),
            (2,), 1)


def _auto_microbatch(cfg, ss, mesh, rules) -> int:
    """Pick the smallest grad-accumulation factor whose remat activation
    stack fits the HBM budget (recorded per-cell; a SSPerf lever)."""
    dp = 1
    batch_ax = rules.get("batch") or ()
    batch_ax = (batch_ax,) if isinstance(batch_ax, str) else tuple(batch_ax)
    for a in batch_ax:
        if a in mesh.shape:
            dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    tok_loc = ss.global_batch * ss.seq_len / max(dp, 1)
    per_tok = cfg.d_model * 2 * cfg.n_layers               # remat stack, bf16
    per_tok += 3 * 4 * cfg.vocab / max(tp, 1)              # f32 logits + grad
    if cfg.moe_every:                                      # dispatch buffers
        per_tok += cfg.top_k * cfg.d_model * 2 * 4
    if cfg.rwkv or cfg.attn_every:                         # ssm chunk states
        per_tok *= 1.5
    budget = 5.5e9
    need = tok_loc * per_tok
    best = 1
    for n in (1, 2, 4, 8, 16, 32):
        if ss.global_batch % n == 0 and ss.global_batch // n >= dp:
            best = n
            if need / n <= budget:
                return n
    return best


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_override: Optional[Dict[str, Any]] = None,
             save_dir: Optional[str] = None,
             verbose: bool = True) -> Dict[str, Any]:
    if arch == "vu_systolic":
        return run_ea_cell(multi_pod, save_dir, verbose)
    cfg = cbase.get_arch(arch)
    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    out: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_tag,
        "params_b": cfg.param_count(),
    }
    if not shape_applicable(cfg, shape_name):
        out["status"] = "skipped"
        out["reason"] = ("long_500k requires sub-quadratic attention; "
                         "skip documented in DESIGN.md SSArch-applicability")
        _save(out, save_dir)
        return out

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = logical.default_rules(multi_pod)
    if shape_name == "long_500k":
        # B=1: the data axis is idle for batch; spend it on KV sequence
        rules = rules.override(kv_seq=("data", "model"), batch=None)
    if rules_override:
        rules = rules.override(**rules_override)
    out["rules"] = {k: v for k, v in rules.table}

    t0 = time.time()
    try:
        with logical.activate(mesh, rules):
            built = build_lowerable(cfg, shape_name, mesh, rules)
            fn, args, donate, n_micro = built
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        text = compiled.as_text()
        walk = hloparse.analyze(text)      # trip-count-aware per-device walk
        chips = 512 if multi_pod else 256

        flops_dev = float(walk["flops"])             # dot flops, loop-scaled
        flops_dev_xla = float(ca.get("flops", 0.0))  # raw (loops counted 1x)
        bytes_dev = float(walk["traffic_bytes"])
        coll_dev = float(walk["total"])

        ss = SHAPES[shape_name]
        model_fl = cm.model_flops_per_step(cfg, ss)

        terms = {
            "compute_s": flops_dev / PEAK_FLOPS,
            "memory_s": bytes_dev / HBM_BW,
            "collective_s": coll_dev / ICI_BW,
        }
        out.update(
            status="ok",
            n_micro=n_micro,
            lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
            memory={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
                "peak_estimate_bytes": (ma.argument_size_in_bytes
                                        + ma.temp_size_in_bytes
                                        + ma.output_size_in_bytes
                                        - ma.alias_size_in_bytes),
            },
            cost={"flops_per_device": flops_dev,
                  "flops_per_device_xla_raw": flops_dev_xla,
                  "bytes_per_device": bytes_dev},
            collectives={k: float(walk[k])
                         for k in hloparse.COLLECTIVES + ("total",)},
            roofline=dict(
                terms,
                dominant=max(terms, key=terms.get),
                model_flops=model_fl,
                hlo_flops_global=flops_dev * chips,
                useful_ratio=(model_fl / (flops_dev * chips)
                              if flops_dev else 0.0),
            ),
        )
    except Exception as e:  # noqa: BLE001 -- a failing cell is a bug report
        out.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    if verbose:
        st = out["status"]
        if st == "ok":
            r = out["roofline"]
            print(f"[{mesh_tag}] {arch:22s} {shape_name:12s} OK "
                  f"compile={out['compile_s']:.0f}s "
                  f"peak={out['memory']['peak_estimate_bytes']/2**30:.2f}GiB "
                  f"dom={r['dominant']:12s} useful={r['useful_ratio']:.2f}",
                  flush=True)
        else:
            print(f"[{mesh_tag}] {arch:22s} {shape_name:12s} {st}: "
                  f"{out.get('reason', out.get('error'))}", flush=True)
    _save(out, save_dir)
    return out


def run_ea_cell(multi_pod: bool, save_dir: Optional[str],
                verbose: bool = True) -> Dict[str, Any]:
    """The paper's own workload on the production mesh: one NSGA-II island
    round (evolve + ring migration) per device over the whole pod --
    256 (single-pod) / 512 (multi-pod) islands of the VU11P placement."""
    from repro.core import evolve, nsga2
    from repro.fpga import device as fdev, netlist

    mesh_tag = "pod2x16x16" if multi_pod else "pod16x16"
    out: Dict[str, Any] = {"arch": "vu_systolic", "shape": "ea_round",
                           "mesh": mesh_tag, "params_b": 0}
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(mesh.axis_names)
    # xcvu_test keeps the placeholder-device execution tractable on one CPU
    # simulating 256/512 chips; the mesh/collective structure is identical
    # to the VU11P production run (same shard_map, same ring migration)
    prob = netlist.make_problem(fdev.get_device("xcvu_test"))
    t0 = time.time()
    try:
        # the EA is cheap enough to EXECUTE on the placeholder devices --
        # one island per chip across the whole pod, ring migration live
        st, hist = evolve.run_islands(
            prob, "nsga2", nsga2.NSGA2Config(pop_size=16),
            jax.random.PRNGKey(0), rounds=1, gens_per_round=2,
            mesh=mesh, axis=axes)
        jax.block_until_ready(hist)
        out.update(status="ok", compile_s=round(time.time() - t0, 2),
                   lower_s=0.0, n_micro=1,
                   memory={"argument_bytes": 0, "output_bytes": 0,
                           "temp_bytes": 0, "alias_bytes": 0,
                           "peak_estimate_bytes": 0},
                   cost={}, collectives={"total": 0.0},
                   roofline={"note": "EA islands execute (not just lower) "
                             "on the placeholder mesh", "dominant": "n/a"},
                   best_objs=[float(x) for x in
                              __import__("numpy").asarray(hist)[-1].min(0)])
    except Exception as e:  # noqa: BLE001
        out.update(status="error", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    if verbose:
        print(f"[{mesh_tag}] vu_systolic            ea_round     "
              f"{out['status']} ({out.get('compile_s', 0)}s, "
              f"{mesh.devices.size} islands)", flush=True)
    _save(out, save_dir)
    return out


def _save(out: Dict[str, Any], save_dir: Optional[str]):
    if not save_dir:
        return
    os.makedirs(save_dir, exist_ok=True)
    path = os.path.join(
        save_dir, f"{out['arch']}__{out['shape']}__{out['mesh']}.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--rules", default=None,
                    help="JSON logical-rule overrides, e.g. "
                         "'{\"kv_seq\": [\"data\",\"model\"]}'")
    args = ap.parse_args()

    overrides = None
    if args.rules:
        overrides = {
            k: (tuple(v) if isinstance(v, list) else v)
            for k, v in json.loads(args.rules).items()}

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    cells = []
    if args.all:
        for arch in cbase.ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    n_bad = 0
    for mp in meshes:
        for arch, shape in cells:
            res = run_cell(arch, shape, mp, overrides, args.out)
            if res["status"] == "error":
                n_bad += 1
    if n_bad:
        raise SystemExit(f"{n_bad} dry-run cells failed")


if __name__ == "__main__":
    main()
