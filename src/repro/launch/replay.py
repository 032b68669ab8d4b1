"""Open-loop trace replay with deterministic fault injection.

Feeds a Philly-derived arrival process (``repro.serve.replay``) through
the serve engine at a configurable load while a seeded
``FaultInjector`` (``repro.serve.chaos``) applies a declarative fault
schedule keyed to the engine's decode-step clock. Records PR 7 event
traces, and with ``--verify`` asserts the exactness invariant: every
non-dropped request's greedy output is token-identical to the
fault-free K=1 single-device static reference.

Example — 3-fault chaos smoke on the host mesh::

    PYTHONPATH=src python -m repro.launch.replay \\
        --arch qwen2-0.5b --cache paged --mesh host --slots 8 \\
        --n 16 --load 2.0 --max-len 64 --prompt-len 12 --max-new 8 \\
        --faults "slot_kill@8,prefix_flush@12,pool_shrink@16:blocks=6" \\
        --trace /tmp/replay_trace.jsonl --verify

Fault specs are ``kind@step[:key=val...]`` (comma-separated) or a JSON
schedule file via ``--faults-file`` (see ``FaultSchedule.to_json``).
"""
import os
import sys

from repro.launch._bootstrap import (force_host_devices, mesh_flag,
                                    use_compile_cache)

use_compile_cache()
# CPU-only test path: --mesh host on the CPU backend needs forced host
# devices; on a TPU host the flag changes nothing.
if mesh_flag(sys.argv) == "host":
    force_host_devices(os.environ.get("REPRO_SERVE_DEVICES", "8"))

import jax  # noqa: E402  (lock the device count before any repro import)

import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402

from repro.configs import ARCH_IDS, get_config                    # noqa: E402
from repro.serve import (FaultInjector, FaultSchedule,            # noqa: E402
                         ServeEngine, philly_requests, run_replay,
                         sharded_engine)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b", choices=ARCH_IDS)
    ap.add_argument("--preset", default="smoke", choices=["smoke", "full"])
    ap.add_argument("--cache", default="paged",
                    choices=["contiguous", "paged"])
    ap.add_argument("--mesh", default="single", choices=["single", "host"])
    ap.add_argument("--policy", default="fcfs",
                    choices=["fcfs", "sjf", "slo"])
    ap.add_argument("--n", type=int, default=16,
                    help="number of Philly-derived requests in the replay")
    ap.add_argument("--load", type=float, default=2.0,
                    help="mean open-loop arrival rate in requests per "
                         "decode step (Poisson)")
    ap.add_argument("--seed", type=int, default=7,
                    help="workload seed: arrivals, prompt contents, budgets")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-schedule seed: victim picks, burst contents")
    ap.add_argument("--faults", default="",
                    help="comma-separated fault specs, each "
                         "'kind@step[:key=val...]', e.g. "
                         "'slot_kill@8,pool_shrink@16:blocks=6'")
    ap.add_argument("--faults-file", default=None, metavar="PATH",
                    help="JSON fault schedule (overrides --faults)")
    ap.add_argument("--slots", type=int, default=4,
                    help="cache-pool slots (continuous engine)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV positions per block (paged cache)")
    ap.add_argument("--blocks", type=int, default=0,
                    help="paged pool size in blocks "
                         "(0 = slots * ceil(max_len / block_size))")
    ap.add_argument("--watermark", type=float, default=0.05,
                    help="fraction of blocks reserved at admission (paged)")
    ap.add_argument("--prefill-lanes", type=int, default=4,
                    help="joining requests prefilled per jitted chunk-round "
                         "(paged cache)")
    ap.add_argument("--no-prefix-cache", dest="prefix_cache",
                    action="store_false",
                    help="disable content-hashed prompt-block sharing (paged)")
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max prompt length (GPU demand scales in [len/2, "
                         "len])")
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--decode-horizon", type=int, default=8,
                    help="decode steps per jitted dispatch (the injector "
                         "caps this so faults land on their step)")
    ap.add_argument("--eos-token", type=int, default=None,
                    help="stop a request early when it emits this token id")
    ap.add_argument("--max-admit-retries", type=int, default=4,
                    help="admission retries with exponential backoff before "
                         "a request is dropped during pool_shrink")
    ap.add_argument("--elastic", action="store_true",
                    help="install an ElasticController: proactive scale "
                         "up/down from the occupancy/queue/slack gauges, "
                         "on top of reactive device_fail/device_join "
                         "recovery")
    ap.add_argument("--elastic-max-units", type=int, default=None,
                    help="proactive scale-up capacity ceiling in cache "
                         "units (default: the pool's constructed size)")
    ap.add_argument("--elastic-min-units", type=int, default=None,
                    help="proactive scale-down floor (default: no "
                         "proactive shrink below the constructed size)")
    ap.add_argument("--elastic-step-units", type=int, default=8,
                    help="cache units moved per proactive reshape")
    ap.add_argument("--elastic-cooldown", type=float, default=16.0,
                    help="decode steps between reshapes (shared between "
                         "proactive decisions and chaos recovery)")
    ap.add_argument("--verify", action="store_true",
                    help="check every non-dropped output against the "
                         "fault-free single-device static engine")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="dump a structured event trace of the replay here "
                         "(analyze with repro.launch.trace_report)")
    ap.add_argument("--trace-format", default="jsonl",
                    choices=["jsonl", "chrome"])
    ap.add_argument("--trace-capacity", type=int, default=1 << 16)
    ap.add_argument("--metrics-every", type=int, default=1)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.preset == "smoke")

    if args.faults_file:
        schedule = FaultSchedule.from_json(args.faults_file)
    else:
        schedule = FaultSchedule.from_spec(args.faults, seed=args.chaos_seed)
    injector = FaultInjector(schedule, seed=args.chaos_seed)

    reqs = philly_requests(cfg.vocab_size, args.n, load=args.load,
                           seed=args.seed, prompt_len=args.prompt_len,
                           max_new=args.max_new, max_len=args.max_len)

    tracer = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer(capacity=args.trace_capacity)

    elastic = None
    if args.elastic:
        from repro.serve import ElasticController
        elastic = ElasticController(step_units=args.elastic_step_units,
                                    max_units=args.elastic_max_units,
                                    min_units=args.elastic_min_units,
                                    cooldown=args.elastic_cooldown)

    engine_kw = dict(cache=args.cache, block_size=args.block_size,
                     n_blocks=args.blocks or None,
                     watermark=args.watermark,
                     prefill_lanes=args.prefill_lanes,
                     prefix_cache=args.prefix_cache,
                     decode_horizon=args.decode_horizon,
                     eos_token=args.eos_token,
                     injector=injector, elastic=elastic,
                     max_admit_retries=args.max_admit_retries,
                     tracer=tracer, metrics_every=args.metrics_every)

    if args.mesh == "host":
        engine = sharded_engine(cfg, n_slots=args.slots,
                                max_len=args.max_len, policy=args.policy,
                                **engine_kw)
    else:
        engine = ServeEngine(cfg, max_len=args.max_len, n_slots=args.slots,
                             policy=args.policy, **engine_kw)

    res = run_replay(engine, reqs, verify=args.verify, ref_cfg=cfg,
                     ref_max_len=args.max_len)

    trace_info = None
    if tracer is not None:
        if args.trace_format == "chrome":
            from repro.obs import write_chrome_trace
            write_chrome_trace(args.trace, tracer.events)
        else:
            tracer.dump_jsonl(args.trace)
        trace_info = {"path": args.trace, "format": args.trace_format,
                      "events": len(tracer), "dropped": tracer.dropped}

    record = {
        "arch": cfg.arch_id,
        "cache": args.cache,
        "mesh": args.mesh,
        "policy": args.policy,
        "n_devices": jax.device_count(),
        "slots": args.slots,
        "load": args.load,
        "n_requests": len(res.requests),
        "faults": [{"kind": k, "step": s} for k, s in res.faults],
        "dropped_ids": res.dropped,
        "elastic": bool(elastic),
        **dataclasses.asdict(res.stats),
    }
    if trace_info is not None:
        record["trace"] = trace_info
    if args.verify:
        record["verified"] = bool(res.verified)
        record["mismatched"] = res.mismatched
    print(json.dumps(record, indent=2, default=float))

    if args.verify and not res.verified:
        raise SystemExit(
            f"FAIL: {len(res.mismatched)} non-dropped request(s) diverged "
            f"from the fault-free reference: {res.mismatched}")


if __name__ == "__main__":
    main()
