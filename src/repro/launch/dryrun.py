"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) combination with ShapeDtypeStruct inputs — no allocation — and extract
the roofline terms from the compiled artifact.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape train_4k --mesh pod --out experiments/dryrun.jsonl

The XLA_FLAGS bootstrap below is the FIRST executable statement — before
any jax import (device count is locked at first init). REPRO_DRYRUN_DEVICES
overrides the forced device count (CI smoke runs use 8 with --mesh host);
when jax is already imported (in-process test usage) the flag is left alone.
"""
import os
import sys

from repro.launch._bootstrap import force_host_devices, mesh_flag

force_host_devices(os.environ.get(
    "REPRO_DRYRUN_DEVICES", "8" if mesh_flag(sys.argv) == "host" else "512"))

import argparse
import json
import re
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config
from repro.dist import sharding as shd
from repro.launch.mesh import (CHIP_PEAKS, TARGET_KIND, axis_sizes,
                               make_host_mesh, make_production_mesh)
from repro.models.api import build_model, cache_specs, input_specs, params_specs
from repro.train import state as state_lib
from repro.train.optimizer import adamw, constant

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s32": 4, "u32": 4,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes_from_hlo(hlo: str) -> Dict[str, float]:
    """Sum output-shape bytes of every collective op in the (post-SPMD) HLO.

    Approximation documented in EXPERIMENTS.md: bytes-on-the-wire per chip is
    ~(output bytes) for all-reduce (ring: 2(n-1)/n ~ 2x input) and
    ~(gathered bytes x (n-1)/n) for all-gather; we report raw output bytes
    per op kind and fold the ring factors into the roofline term.
    """
    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    for line in hlo.splitlines():
        stripped = line.strip().lstrip("%")
        for kind in _COLLECTIVES:
            # match "= <shape> kind(" including fusion-wrapped ("...-start")
            if re.search(rf"= [^=]*\b{kind}(-start)?\(", stripped):
                eq = stripped.split("=", 1)[1]
                lhs = eq.split(kind, 1)[0]
                for dt, dims in _SHAPE_RE.findall(lhs):
                    if dt not in _DTYPE_BYTES:
                        continue
                    n = 1
                    if dims:
                        for d in dims.split(","):
                            n *= int(d)
                    out[kind] += n * _DTYPE_BYTES[dt]
                break
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _batch_axes(mesh) -> tuple:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _div(n: int, size: int) -> bool:
    return n % size == 0 and n > 0


def cache_pspecs(cfg, cache_shape, mesh, *, seq_shard: bool, batch: int,
                 paged: bool = False):
    """PartitionSpecs for the decode cache, per family (DESIGN.md §7).

    KV head counts that do not divide the model axis fall back to sharding
    the cache SEQ dimension over 'model' (whisper kv=20, qwen2-7b kv=4,
    phi3.5-moe kv=8 at 32k x batch 128 do not fit HBM otherwise); decode
    attention handles a seq-sharded KV via partial-softmax all-reduce.

    ``paged=True`` describes the head-major block-pool layout instead: k/v
    leaves are ``[L, n_blocks, kv, block_size, hd]`` — the block dimension
    stays unsharded (any slot's table may name any block, so blocks must be
    addressable without a gather collective), KV heads shard over 'model',
    and the small-KV-head fallback shards the in-block position dimension."""
    ba = _batch_axes(mesh)
    bsz = 1
    for a in ba:
        bsz *= axis_sizes(mesh)[a]
    b_ax = ba if _div(batch, bsz) else None
    msize = axis_sizes(mesh)["model"]

    def spec_for(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        shape = leaf.shape
        def m_ax(dim):
            return "model" if _div(shape[dim], msize) else None
        if paged and name in ("k", "v"):
            # [L, NB, kv, BS, hd]
            s_ax = ("model" if m_ax(2) is None and _div(shape[3], msize)
                    else None)
            return P(None, None, m_ax(2), s_ax, None)
        if name in ("k", "v") or name.endswith(("attn_k", "attn_v")):
            # [L_or_G, B, S, kv, hd]
            if seq_shard:
                s_ax = "data"
            elif m_ax(3) is None and _div(shape[2], msize):
                s_ax = "model"
            else:
                s_ax = None
            return P(None, b_ax, s_ax, m_ax(3), None)
        if name in ("ck", "cv"):
            return P(None, b_ax, None, m_ax(3), None)
        if name.endswith("conv") and leaf.ndim == 4:     # [L,B,K-1,ch]
            return P(None, b_ax, None, m_ax(3))
        if name.endswith("conv") and leaf.ndim == 5:     # [G,E,B,K-1,ch]
            return P(None, None, b_ax, None, m_ax(4))
        if name.endswith("ssm") and leaf.ndim == 5:      # [L,B,H,N,P]
            return P(None, b_ax, m_ax(2), None, None)
        if name.endswith("ssm") and leaf.ndim == 6:      # [G,E,B,H,N,P]
            return P(None, None, b_ax, m_ax(3), None, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec_for, cache_shape)


def opt_state_pspecs(param_specs_tree, params_shape, mesh):
    """ZeRO-1: shard optimizer moments over the data axes on top of the
    param's own spec (first unsharded, divisible dimension)."""
    ba = _batch_axes(mesh)
    sizes = axis_sizes(mesh)
    dsz = 1
    for a in ba:
        dsz *= sizes[a]

    def zero1(spec, leaf):
        parts = list(spec) + [None] * (leaf.ndim - len(spec))
        for i, (p_, d) in enumerate(zip(parts, leaf.shape)):
            if p_ is None and d % dsz == 0 and d > 0:
                parts[i] = ba if len(ba) > 1 else ba[0]
                break
        return P(*parts)

    return jax.tree_util.tree_map(zero1, param_specs_tree, params_shape,
                                  is_leaf=lambda x: isinstance(x, P))


def _probe_plan(arch: str) -> tuple:
    """(probe layer counts, extra overrides per probe, effective full L).

    XLA cost_analysis counts while-loop bodies once, so per-layer FLOP/byte/
    collective slopes are measured on small UNROLLED probe configs and
    extrapolated linearly: total = f(la) + slope * (L_full - la).
    """
    cfg = get_config(arch)
    if arch == "gemma3-27b":
        # preserve the 5:1 local:global pattern (global_every=6)
        return (6, 12), {}, cfg.n_layers
    if cfg.family == "hybrid":
        # multiples of shared_attn_every (6): 1 and 2 super-groups
        return (6, 12), {}, cfg.n_layers
    if cfg.family == "encdec":
        return (2, 4), {"scale_enc": True}, cfg.n_layers
    return (2, 4), {}, cfg.n_layers


def probe_slopes(arch: str, shape_name: str, multi_pod: bool, *,
                 zero1: bool, remat: str, extra_cfg: Optional[dict] = None,
                 mesh_kind: Optional[str] = None) -> Dict[str, float]:
    (la, lb), opts, l_full = _probe_plan(arch)
    vals = {}
    for l in (la, lb):
        ov = dict(extra_cfg or {})
        ov.update(n_layers=l, unroll=True)
        if opts.get("scale_enc"):
            ov["n_enc_layers"] = l
        rec, _ = lower_combo(arch, shape_name, multi_pod, zero1=zero1,
                             remat=remat, extra_cfg=ov, probe=False,
                             mesh_kind=mesh_kind)
        vals[l] = rec
    out = {}
    for key in ("flops_per_chip", "bytes_per_chip", "wire_bytes_per_chip"):
        fa, fb = vals[la][key], vals[lb][key]
        slope = (fb - fa) / (lb - la)
        out[key] = fa + slope * (l_full - la)
        out[key + "_slope"] = slope
    out["probe_layers"] = [la, lb]
    out["probe_compile_s"] = sum(v["compile_s"] + v["lower_s"]
                                 for v in vals.values())
    return out


def sharded_arg_bytes(shape_tree, spec_tree, mesh) -> float:
    """Analytic per-device bytes of the program arguments (the reliable
    'does it fit' number — CPU memory_analysis reports are inconsistent)."""
    sizes = axis_sizes(mesh)

    def leaf_bytes(leaf, spec):
        denom = 1
        for part in (spec or P()):
            if part is None:
                continue
            for ax in (part if isinstance(part, (tuple, list)) else (part,)):
                denom *= sizes[ax]
        n = 1
        for d in leaf.shape:
            n *= d
        return n * jnp.dtype(leaf.dtype).itemsize / denom

    total = 0.0
    leaves, _ = jax.tree_util.tree_flatten(shape_tree)
    specs, _ = jax.tree_util.tree_flatten(
        spec_tree, is_leaf=lambda x: isinstance(x, P))
    for leaf, spec in zip(leaves, specs):
        total += leaf_bytes(leaf, spec)
    return total


def lower_combo(arch: str, shape_name: str, multi_pod: bool,
                *, zero1: bool = True, remat: str = "full",
                extra_cfg: Optional[dict] = None, probe: bool = True,
                mesh_kind: Optional[str] = None):
    """Build + lower + compile one combination; returns (record, compiled).

    ``mesh_kind="host"`` targets whatever devices the host exposes (CI smoke
    on a forced 8-device CPU); default is the production pod/multipod mesh.
    """
    t_start = time.time()
    mesh = (make_host_mesh() if mesh_kind == "host"
            else make_production_mesh(multi_pod=multi_pod))
    ishape = INPUT_SHAPES[shape_name]
    seq_shard = shape_name == "long_500k"
    table = shd.production_rules_table(multi_pod, seq_shard=seq_shard)
    if (ishape.mode == "decode" and not seq_shard):
        pre_cfg = get_config(arch, **(extra_cfg or {}))
        msize = axis_sizes(mesh)["model"]
        if pre_cfg.n_kv_heads and pre_cfg.n_kv_heads % msize != 0:
            table["kv_seq"] = "model"

    overrides = dict(dtype="bfloat16", param_dtype="bfloat16")
    if ishape.mode == "train":
        overrides["remat"] = remat
    if extra_cfg:
        overrides.update(extra_cfg)
    cfg = get_config(arch, **overrides)
    if shape_name == "long_500k" and not cfg.supports_long_decode:
        raise SystemExit(f"SKIP: {arch} does not support long_500k (full "
                         f"attention — see DESIGN.md)")

    model = build_model(cfg)
    with shd.axis_rules(mesh, table) as rules:
        pshape = params_specs(cfg)
        pspec = shd.param_pspecs(pshape, rules)
        psharding = shd.named(pspec, mesh)
        batch_specs = input_specs(cfg, ishape.global_batch, ishape.seq_len,
                                  ishape.mode)
        bsz = ishape.global_batch
        ba = _batch_axes(mesh)
        basz = 1
        for a in ba:
            basz *= axis_sizes(mesh)[a]
        b_ax = (ba if len(ba) > 1 else ba[0]) if _div(bsz, basz) else None
        bsharding = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, P(b_ax, *([None] * (len(s.shape) - 1)))),
            batch_specs)

        if ishape.mode == "train":
            optimizer = adamw(constant(1e-4))
            state_shape = jax.eval_shape(
                lambda p: state_lib.create(p, optimizer), pshape)
            ospec = (opt_state_pspecs(pspec, pshape, mesh) if zero1 else pspec)
            state_spec = {"params": pspec,
                          "opt": {"mu": ospec, "nu": ospec},
                          "step": P()}
            state_sharding = shd.named(state_spec, mesh)
            step_fn = state_lib.make_train_step(model.loss, optimizer)
            jitted = jax.jit(step_fn,
                             in_shardings=(state_sharding, bsharding),
                             out_shardings=(state_sharding, None),
                             donate_argnums=(0,))
            lowered = jitted.lower(state_shape, batch_specs)
            args_bytes = sharded_arg_bytes(state_shape, state_spec, mesh)
        elif ishape.mode == "prefill":
            def fwd(params, batch):
                return model.forward(params, batch)
            jitted = jax.jit(fwd, in_shardings=(psharding, bsharding),
                             out_shardings=None)
            lowered = jitted.lower(pshape, batch_specs)
            args_bytes = sharded_arg_bytes(pshape, pspec, mesh)
        else:  # decode
            cshape = cache_specs(cfg, bsz, ishape.seq_len)
            cspec = cache_pspecs(cfg, cshape, mesh, seq_shard=seq_shard,
                                 batch=bsz)
            csharding = shd.named(cspec, mesh)
            tok_sharding = NamedSharding(mesh, P(b_ax, None))

            def serve_step(params, cache, tokens, pos):
                return model.decode_step(params, cache, tokens, pos)

            jitted = jax.jit(
                serve_step,
                in_shardings=(psharding, csharding, tok_sharding, None),
                out_shardings=(None, csharding),
                donate_argnums=(1,))
            tok_spec = jax.ShapeDtypeStruct((bsz, 1), jnp.int32)
            pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
            lowered = jitted.lower(pshape, cshape, tok_spec, pos_spec)
            args_bytes = (sharded_arg_bytes(pshape, pspec, mesh)
                          + sharded_arg_bytes(cshape, cspec, mesh))

        t_lower = time.time()
        compiled = lowered.compile()
        t_compile = time.time()

    n_chips = mesh.devices.size
    cost = compiled.cost_analysis() or {}
    try:
        mem = compiled.memory_analysis()
        mem_stats = {
            "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
        }
    except Exception as e:          # CPU backend may not implement it
        mem_stats = {"error": str(e)}

    coll = collective_bytes_from_hlo(compiled.as_text())

    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    # ring all-reduce moves ~2x bytes; others ~1x; per-chip wire bytes
    wire = (2.0 * coll["all-reduce"] + coll["all-gather"]
            + coll["reduce-scatter"] + coll["all-to-all"]
            + coll["collective-permute"])

    # cost_analysis counts while(scan) bodies ONCE — recover true totals from
    # unrolled two-point probes (see probe_slopes); skip for probe compiles.
    probe_stats = None
    if probe:
        probe_stats = probe_slopes(arch, shape_name, multi_pod, zero1=zero1,
                                   remat=remat, extra_cfg=extra_cfg,
                                   mesh_kind=mesh_kind)
        flops = probe_stats["flops_per_chip"]
        bytes_accessed = probe_stats["bytes_per_chip"]
        wire = probe_stats["wire_bytes_per_chip"]

    # the roofline of the chip the production meshes describe, not of the
    # host this compile ran on
    peaks = CHIP_PEAKS[TARGET_KIND]
    compute_s = flops / peaks["flops_bf16"]
    memory_s = bytes_accessed / peaks["hbm_bw"]
    collective_s = wire / peaks["ici_bw"]

    n = get_config(arch).param_count()
    n_active = get_config(arch).param_count(active_only=True)
    tokens = ishape.global_batch * (ishape.seq_len if ishape.mode != "decode"
                                    else 1)
    mult = 6 if ishape.mode == "train" else 2
    model_flops = mult * n_active * tokens
    model_flops_per_chip = model_flops / n_chips

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind or ("multipod" if multi_pod else "pod"),
        "n_chips": n_chips,
        "mode": ishape.mode,
        "zero1": zero1,
        "remat": remat if ishape.mode == "train" else None,
        "lower_s": round(t_lower - t_start, 1),
        "compile_s": round(t_compile - t_lower, 1),
        "flops_per_chip": flops,
        "bytes_per_chip": bytes_accessed,
        "collective_bytes": {k: v for k, v in coll.items()},
        "wire_bytes_per_chip": wire,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "bottleneck": max(("compute", compute_s), ("memory", memory_s),
                          ("collective", collective_s), key=lambda t: t[1])[0],
        "model_flops_per_chip": model_flops_per_chip,
        "useful_flop_ratio": (model_flops_per_chip / flops) if flops else None,
        "memory_stats": mem_stats,
        "args_gib_per_device": round(args_bytes / 2**30, 3),
        "params": n,
        "params_active": n_active,
        "probe": probe_stats,
    }
    return record, compiled


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "host"])
    ap.add_argument("--out", default=None)
    ap.add_argument("--no-zero1", action="store_true")
    ap.add_argument("--no-probe", action="store_true",
                    help="skip unrolled flop probes (multipod pass/fail runs)")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--cfg-json", default=None,
                    help="JSON dict of ArchConfig overrides (perf iterations)")
    ap.add_argument("--tag", default=None)
    args = ap.parse_args()

    extra = json.loads(args.cfg_json) if args.cfg_json else None
    record, compiled = lower_combo(
        args.arch, args.shape, args.mesh == "multipod",
        zero1=not args.no_zero1, remat=args.remat, extra_cfg=extra,
        probe=not args.no_probe,
        mesh_kind="host" if args.mesh == "host" else None)
    if args.tag:
        record["tag"] = args.tag

    print(json.dumps({k: v for k, v in record.items()
                      if k != "memory_stats"}, indent=2))
    print("memory:", record["memory_stats"])
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(record) + "\n")


if __name__ == "__main__":
    main()
