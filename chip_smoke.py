"""Smoke test of the serve path on a TPU: the quickest proof that the
system still starts on the chip.

    python chip_smoke.py               # one chip: kernels, qwen2-0.5b, mamba2-780m
    python chip_smoke.py --four-chip   # four chips: sharded qwen2-0.5b vs one chip

One chip runs three phases at the published widths, with random weights
made from a seed:

* kernels: paged decode, paged prefill and flash attention at qwen2-0.5b
  widths, and the SSD scan at mamba2-780m widths, through ``kernels/ops.py``
  against their ``kernels/ref.py`` oracles;
* qwen2-0.5b (bf16, Pallas kernels) served through the paged continuous
  engine with open-loop arrivals, its prompt logits checked against an
  engine without the kernels;
* mamba2-780m (bf16, Pallas SSD) served through the contiguous engine,
  checked the same way against a limit measured from its bf16 rounding
  noise, and again in f32.

``--four-chip`` runs only qwen2-0.5b (f32) through ``sharded_engine`` on a
(2, 2) mesh and the same requests on a one-chip engine, and requires
token-identical greedy outputs.

Everything runs in this one process. The script exits non-zero, and prints
no result, when JAX finds no TPU or any phase fails. Otherwise its last
line is ``{"ok": true, "device": {...}}`` with the device as JAX reports
it. Times are host-clock seconds on the device the last line names.
"""
import os
import sys

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if not os.path.isdir(os.path.join(_SRC, "repro")):
    sys.exit("chip_smoke: src/repro is not beside this script; run it from "
             "a checkout of the repository")
sys.path.insert(0, _SRC)

from repro.launch._bootstrap import use_compile_cache  # noqa: E402

use_compile_cache()

import argparse  # noqa: E402
import json      # noqa: E402
import time      # noqa: E402
import traceback  # noqa: E402

import jax                # noqa: E402
import jax.numpy as jnp   # noqa: E402
import numpy as np        # noqa: E402

from repro.configs import get_config          # noqa: E402
from repro.kernels import ops, ref            # noqa: E402
from repro.launch.serve import make_requests  # noqa: E402
from repro.serve import ServeEngine, ServeRequest, sharded_engine  # noqa: E402

SEED = 0

# Kernel outputs are bf16 attention averages of unit-scale values: one bf16
# ulp near 1 is 2**-7. The oracle runs in f32 at HIGHEST precision from the
# same bf16 inputs and rounds its output to bf16, so the two differ by the
# kernel's own rounding (its f32 softmax weights may enter the MXU as bf16,
# relative 2**-9 per term) plus one output rounding each: 2e-2 is about
# two ulps near 1.
ATTN_TOL = 2e-2
# The SSD scan is all f32 but its matmuls run on the MXU, which may round
# f32 operands to bf16 (relative 2**-9); summed over N=128 state and
# Q=256 positions with random signs the error stays near 2**-8 of the
# output's scale. Checked as max|err| / max|ref|.
SSD_TOL = 1e-2
# Prompt logits of two bf16 engines at full depth, one with the Pallas
# kernels and one without. The kernels keep scores and softmax weights in
# f32 where the XLA path rounds both to bf16, and the two sum in different
# orders, so each layer's residual update differs by a few bf16 roundings
# (relative 2**-9), compounded over 24 (qwen2) or 48 (mamba2) layers. The
# logits are unit-scale (tied embeddings at 1/sqrt(d)) and themselves bf16:
# the largest of 150k lie in [4, 8), where one ulp is 2**-5. 0.125 is four
# of those ulps; a wrong layout or mask moves logits by O(1). A greedy first
# token may differ only at a near-tie: where the reference's top-1 logit
# leads the other engine's pick by no more than this tolerance.
LOGIT_TOL = 0.125
# mamba2-780m amplifies those roundings far more: two bf16 XLA evaluations
# that differ only in the SSD chunk length (the same math summed in another
# order) differ by 0.55 in the logits at 48 layers (CPU run, d 512), and
# the bf16 model differs from its own f32 evaluation by 0.9. So its bf16
# limit is measured, not fixed: twice the bf16 XLA engine's distance from
# an f32 evaluation of the same weights. If the kernel engine is no less
# accurate than the XLA one, the triangle inequality keeps the two within
# that. The kernel itself is held to F32_LOGIT_TOL by the same comparison
# in f32 at HIGHEST precision, where rounding is 2**-24 instead of 2**-9:
# the CPU run gives 2.2e-4 at 48 layers, and a wrong layout or mask moves
# the logits by O(1).
F32_LOGIT_TOL = 1e-2


class CompileClock:
    """Seconds JAX spends in backend compiles (a persistent-cache hit
    counts only its retrieval) and persistent-cache hits, from JAX's own
    monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits


def peak_hbm_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _bf16(key, shape):
    return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)


def _err(out, want):
    return float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                 - want.astype(jnp.float32))))


def _paged_tables(rng, rows, max_blocks, n_blocks, bs, tokens_after):
    """Scattered block tables for rows holding a random number of blocks,
    and each row's last position; ``tokens_after`` positions past it must
    still fall inside the row's blocks."""
    ids = rng.permutation(n_blocks)[:rows * max_blocks].reshape(rows,
                                                                max_blocks)
    used = rng.integers(1, max_blocks + 1, size=rows)
    tables = np.where(np.arange(max_blocks)[None] < used[:, None], ids, -1)
    last = np.array([rng.integers((u - 1) * bs, u * bs - tokens_after + 1)
                     for u in used])
    return jnp.asarray(tables, jnp.int32), last


def kernel_phase(*, hq=14, hkv=2, head_dim=64, block_size=16, slots=8,
                 max_blocks=32, n_blocks=256, seq=512, ssm_heads=48,
                 ssm_headdim=64, ssm_state=128, ssm_chunk=256):
    """Every kernel of the serve path through ops.py against its oracle.
    Defaults: qwen2-0.5b attention and mamba2-780m SSD widths."""
    rng = np.random.default_rng(SEED)
    ks = iter(jax.random.split(jax.random.key(SEED), 16))
    d, bs = head_dim, block_size
    kp = _bf16(next(ks), (n_blocks, hkv, bs, d))
    vp = _bf16(next(ks), (n_blocks, hkv, bs, d))
    out = {}

    tables, pos = _paged_tables(rng, slots, max_blocks, n_blocks, bs, 1)
    q = _bf16(next(ks), (slots, hq, d))
    pos = jnp.asarray(pos, jnp.int32)
    got = ops.paged_attention(q, kp, vp, tables, pos)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_attention(q, kp, vp, tables, pos)
    out["paged_decode"] = _err(got, want)

    # prefill chunks are block-aligned (the engine's lanes start at a
    # block boundary), one block per chunk
    tables, last = _paged_tables(rng, 4, max_blocks, n_blocks, bs, bs)
    start = jnp.asarray(last - last % bs, jnp.int32)
    q = _bf16(next(ks), (4, bs, hq, d))
    got = ops.paged_prefill_attention(q, kp, vp, tables, start)
    with jax.default_matmul_precision("highest"):
        want = ref.paged_prefill_attention(q, kp, vp, tables, start)
    out["paged_prefill"] = _err(got, want)

    q = _bf16(next(ks), (2, seq, hq, d))
    k = _bf16(next(ks), (2, seq, hkv, d))
    v = _bf16(next(ks), (2, seq, hkv, d))
    got = ops.flash_attention(q, k, v, causal=True)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(q, k, v, causal=True)
    out["flash"] = _err(got, want)

    shape = (1, seq, ssm_heads)
    xdt = jax.random.normal(next(ks), shape + (ssm_headdim,))
    a_log = -jax.nn.softplus(jax.random.normal(next(ks), shape))
    b = jax.random.normal(next(ks), shape + (ssm_state,)) * 0.5
    c = jax.random.normal(next(ks), shape + (ssm_state,)) * 0.5
    got = ops.ssd_scan(xdt, a_log, b, c, chunk=ssm_chunk)
    with jax.default_matmul_precision("highest"):
        want = ref.ssd(xdt, a_log, b, c)
    out["ssd_rel"] = _err(got, want) / float(jnp.max(jnp.abs(want)))

    for name in ("paged_decode", "paged_prefill", "flash"):
        check(out[name] <= ATTN_TOL,
              f"{name}: max |kernel - ref| = {out[name]} > {ATTN_TOL}")
    check(out["ssd_rel"] <= SSD_TOL,
          f"ssd_scan: max |kernel - ref| / max |ref| = {out['ssd_rel']} "
          f"> {SSD_TOL}")
    return out


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------
class FirstCall:
    """Wrap a jitted engine function and keep the abstract arguments of its
    first call, so the compiled program can be inspected afterwards."""

    def __init__(self, fn):
        self.fn, self.args, self.kw = fn, None, None

    def __call__(self, *args, **kw):
        if self.args is None:
            self.args = jax.tree_util.tree_map(
                lambda a: (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                sharding=a.sharding)
                           if isinstance(a, jax.Array) else a), args)
            self.kw = kw
        return self.fn(*args, **kw)

    def compiled_text(self):
        check(self.args is not None, "the engine never called this program")
        return self.fn.lower(*self.args, **self.kw).compile().as_text()


def _requests(cfg, n, prompt_len, new_tokens, arrival_rate, prompt_lens=None):
    """``make_requests`` (the serve CLI's generator), then a mixed token
    budget per request in ``new_tokens`` = (lo, hi); ``prompt_lens`` pins
    the prompt lengths to a few values (contiguous prefill compiles one
    program per length)."""
    reqs = make_requests(cfg, n, prompt_len, new_tokens[1], arrival_rate,
                         seed=SEED)
    rng = np.random.default_rng(SEED + 1)
    for i, r in enumerate(reqs):
        if prompt_lens:
            r.prompt = np.resize(r.prompt, prompt_lens[i % len(prompt_lens)])
        r.max_new_tokens = int(rng.integers(new_tokens[0],
                                            new_tokens[1] + 1))
    return reqs


def _copies(reqs, max_new=None):
    return [ServeRequest(r.prompt.copy(),
                         max_new_tokens=max_new or r.max_new_tokens,
                         arrival_time=r.arrival_time) for r in reqs]


def serve_phase(cfg, clock, *, n_requests, prompt_len, new_tokens, n_slots,
                arrival_rate, cache, mosaic_in, prompt_lens=None,
                f32_floor=False, **engine_kw):
    """Serve ``n_requests`` through a continuous engine built with the
    Pallas kernels as the serve CLI builds it: one cold run (compiles), one
    warm run (tokens/s). Then the prompt logits against a same-dtype engine
    without the kernels on the same weights, within LOGIT_TOL, or with
    ``f32_floor`` within twice the bf16 XLA engine's distance from f32 and
    with both engines compared again in f32. ``mosaic_in`` names the
    engine programs ("prefill", "horizon") that must hold a compiled
    Pallas kernel."""
    reqs = _requests(cfg, n_requests, prompt_len, new_tokens, arrival_rate,
                     prompt_lens)
    max_len = max(len(r.prompt) for r in reqs) + new_tokens[1]
    max_len = -(-max_len // 16) * 16
    engine = ServeEngine(cfg, max_len=max_len, n_slots=n_slots,
                         policy="fcfs", cache=cache, record_logits=True,
                         **engine_kw)
    engine._prefill = prefill = FirstCall(engine._prefill)
    engine._horizon = horizon = FirstCall(engine._horizon)

    c0 = clock.snapshot()
    out, cold = engine.run(_copies(reqs))
    c1 = clock.snapshot()
    warm, stats = engine.run(_copies(reqs))
    c2 = clock.snapshot()

    for i, (r, w) in enumerate(zip(out, warm)):
        check(len(r.output) == r.max_new_tokens,
              f"request {i}: {len(r.output)} of "
              f"{r.max_new_tokens} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.output),
              "token id out of the vocabulary")
        check(r.prefill_logits is not None
              and np.isfinite(r.prefill_logits).all(),
              "non-finite prompt logits")
        check(w.output == r.output, "warm run diverged from the cold run")
    check(stats.unfinished == 0 and stats.dropped == 0,
          f"{stats.unfinished} unfinished, {stats.dropped} dropped")
    programs = {"prefill": prefill, "horizon": horizon}
    for name in mosaic_in:
        check("tpu_custom_call" in programs[name].compiled_text(),
              f"no Pallas kernel in the compiled {name} program")

    def prompt_pass(c, params):
        """Every request's prompt pass (one token) through an engine built
        on ``c`` and ``params``."""
        eng = ServeEngine(c, params=params, max_len=max_len,
                          n_slots=n_slots, policy="fcfs", cache=cache,
                          record_logits=True, **engine_kw)
        return eng.run(_copies(reqs, max_new=1))[0]

    ref_out = prompt_pass(cfg.replace(use_pallas=False), engine.params)
    extra, tol = {}, LOGIT_TOL
    if f32_floor:
        c32 = cfg.replace(dtype="float32", param_dtype="float32")
        p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                     engine.params)
        with jax.default_matmul_precision("highest"):
            f32_ref = prompt_pass(c32.replace(use_pallas=False), p32)
            f32_out = prompt_pass(c32, p32)
        floor = _max_logit_err(ref_out, f32_ref)
        f32_err = _max_logit_err(f32_out, f32_ref)
        check(f32_err <= F32_LOGIT_TOL,
              f"f32 prompt logits: max |pallas - xla| = {f32_err} > "
              f"{F32_LOGIT_TOL}")
        tol = 2 * floor
        extra = {"bf16_xla_vs_f32": floor, "f32_logit_max_abs_err": f32_err}

    ties = 0
    for r, rr in zip(out, ref_out):
        if r.output[0] != rr.output[0]:
            b = rr.prefill_logits
            gap = float(b[rr.output[0]] - b[r.output[0]])
            check(gap <= tol,
                  f"first token {r.output[0]} vs reference {rr.output[0]} "
                  f"with a logit gap of {gap} > {tol}")
            ties += 1
    err = _max_logit_err(out, ref_out)
    check(err <= tol, f"prompt logits: max |pallas - xla| = {err} > {tol}")

    return {
        "requests": len(out),
        "new_tokens": stats.new_tokens,
        "warm_tokens_per_s": stats.tokens_per_s,
        "warm_wall_s": stats.wall_s,
        "cold_wall_s": cold.wall_s,
        "compile_s": c1[0] - c0[0],
        "compiles": c1[1] - c0[1],
        "cache_hits": c1[2] - c0[2],
        "warm_compiles": c2[1] - c1[1],
        "logit_max_abs_err": err,
        "logit_tol": tol,
        **extra,
        "first_token_near_ties": ties,
        "peak_hbm_bytes": peak_hbm_bytes(),
    }


def _max_logit_err(out, ref_out):
    return max(float(np.max(np.abs(r.prefill_logits - rr.prefill_logits)))
               for r, rr in zip(out, ref_out))


def qwen_phase(clock, **kw):
    cfg = get_config("qwen2-0.5b", dtype="bfloat16", param_dtype="bfloat16",
                     use_pallas=True)
    args = dict(n_requests=32, prompt_len=384, new_tokens=(32, 64),
                n_slots=8, arrival_rate=0.5, cache="paged", block_size=16,
                prefill_lanes=4, decode_horizon=8,
                mosaic_in=("prefill", "horizon"))
    args.update(kw)
    return serve_phase(cfg, clock, **args)


def mamba_phase(clock, **kw):
    cfg = get_config("mamba2-780m", dtype="bfloat16",
                     param_dtype="bfloat16", use_pallas=True)
    args = dict(n_requests=8, prompt_len=384, prompt_lens=(256, 384),
                new_tokens=(32, 64), n_slots=4, arrival_rate=0.5,
                cache="contiguous", decode_horizon=8, f32_floor=True,
                mosaic_in=("prefill",))   # decode is the recurrent step
    args.update(kw)
    return serve_phase(cfg, clock, **args)


def four_chip_phase(clock, *, arch="qwen2-0.5b", n_requests=16,
                    prompt_len=256, new_tokens=(32, 32), n_slots=8,
                    **cfg_kw):
    """qwen2-0.5b in f32 through ``sharded_engine`` on a (2, 2) host mesh
    against the same requests on a one-chip engine: greedy outputs must be
    token-identical (the ``--verify`` contract). Matmuls run at HIGHEST
    precision so both programs compute in f32."""
    from repro.launch.mesh import make_host_mesh
    cfg = get_config(arch, **cfg_kw)
    mesh = make_host_mesh(model_axis=2)
    check(mesh.devices.shape == (2, 2), f"mesh {mesh.devices.shape}")
    reqs = _requests(cfg, n_requests, prompt_len, new_tokens, 0.5)
    max_len = -(-(prompt_len + new_tokens[1]) // 16) * 16
    kw = dict(n_slots=n_slots, max_len=max_len, policy="fcfs",
              cache="paged", block_size=16)
    with jax.default_matmul_precision("highest"):
        sharded = sharded_engine(cfg, mesh=mesh, **kw)
        single = ServeEngine(cfg, params=jax.device_put(
            sharded.params, jax.devices()[0]), **kw)
        c0 = clock.snapshot()
        s_out, s_stats = sharded.run(_copies(reqs))
        c1 = clock.snapshot()
        o_out, o_stats = single.run(_copies(reqs))

    n_dev = len(mesh.devices.flat)
    for leaf in jax.tree_util.tree_leaves(sharded.pool.buffers):
        check(len(leaf.sharding.device_set) == n_dev,
              f"cache leaf on {len(leaf.sharding.device_set)} devices")
    for leaf in jax.tree_util.tree_leaves(sharded.params):
        check(len(leaf.sharding.device_set) == n_dev,
              f"parameter on {len(leaf.sharding.device_set)} devices")
    diverged = [i for i, (a, b) in enumerate(zip(s_out, o_out))
                if a.output != b.output]
    for i, r in enumerate(s_out):
        check(len(r.output) == r.max_new_tokens,
              f"request {i}: {len(r.output)} of "
              f"{r.max_new_tokens} tokens")
    check(not diverged, f"requests {diverged} diverged from one chip")
    return {
        "requests": len(s_out),
        "new_tokens": s_stats.new_tokens,
        "identical": not diverged,
        "sharded_cold_wall_s": s_stats.wall_s,
        "single_cold_wall_s": o_stats.wall_s,
        "sharded_compile_s": c1[0] - c0[0],
        "peak_hbm_bytes_dev0": peak_hbm_bytes(),
    }


# ---------------------------------------------------------------------------
def _run(name, fn, *args):
    t0 = time.perf_counter()
    try:
        res = fn(*args)
    except Exception:                       # noqa: BLE001 — report, go on
        traceback.print_exc()
        print(f"phase {name}: FAILED after "
              f"{time.perf_counter() - t0:.1f} s", flush=True)
        return False
    res["phase_s"] = time.perf_counter() - t0
    print(f"phase {name}: ok {json.dumps(res)}", flush=True)
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded qwen2-0.5b path on a (2, 2) "
                         "mesh and its one-chip comparison")
    args = ap.parse_args()

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chip else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} TPU devices, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {jax.config.jax_compilation_cache_dir}",
          flush=True)

    clock = CompileClock()
    if args.four_chip:
        ok = _run("four_chip_sharded_vs_one", four_chip_phase, clock)
    else:
        ok = all([_run("kernels", kernel_phase),
                  _run("serve_qwen2_0_5b_paged", qwen_phase, clock),
                  _run("serve_mamba2_780m_contiguous", mamba_phase, clock)])
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
