"""Serving driver: continuous-batching engine(s) fed by a synthetic
open-loop client, reporting the survey's serving metrics (QPS, latency
percentiles, TTFT, JCT, SLO attainment).

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --reduced \
        --requests 32 --slots 4 --rate 8

``--slots 0`` derives the slot count and admission flush deadline from the
cost model (repro.core.misd.batching.plan_admission) instead of constants.

``--replicas N`` (N > 1) serves the same traffic through the multi-engine
cluster frontend (repro.serving.cluster): N ServingEngine replicas behind
one SLO-aware (EDF) frontend queue, routed by ``--route-policy``
(round-robin | least-loaded | p2c | predicted). ``--ttft-slo-ms`` tags
every request with a TTFT deadline so the report includes SLO goodput.

``--temperature`` > 0 switches every request to stochastic decode
(optionally bounded by ``--top-k`` / ``--top-p``); request i samples with
seed ``--sample-seed + i``, so a rerun — or the same workload routed to
different replicas — reproduces every stream bit-for-bit.

Observability exports (PR 8):

``--trace-out PATH`` turns on span tracing (engine + frontend stamp a
typed span trace on every request at existing host-sync points) and
writes the whole run as Chrome-trace JSON — open it at
https://ui.perfetto.dev. ``--metrics-out PATH`` writes the merged
metrics registry (counters + mergeable latency histograms) as
Prometheus-style text exposition plus a JSON snapshot at ``PATH.json``.
``--profile-dir DIR`` arms ``jax.profiler`` around the serving loop via
``EngineConfig.profile_dir`` (TensorBoard-loadable XLA trace).
``--trace-sample-n N`` keeps tracing affordable at rate: only every Nth
request (by rid) carries a span trace.

Multi-tenant overload control (PR 9, serving/overload.py):

``--tenants "gold=2:4,bulk=0:1:256:2048"`` declares SLO classes
(``name=tier:weight[:rate_tokens_s[:burst_tokens]]``); the synthetic
client tags requests round-robin across them, the frontend queue
becomes weighted-fair (DRR across tenants, EDF within), and over-rate
submits are refused with a finite ``retry_after_s``. ``--overload``
additionally arms the degradation-ladder detector (shed lowest tier →
brownout → reject-with-retry-after; pooled p99 TTFT vs ``--ttft-slo-ms``
plus cost-model backlog) and the failover circuit breaker.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.core.costmodel import suggest_health_timeout_s
from repro.core.mimd.router import POLICIES
from repro.models import init_params
from repro.serving import (
    CircuitBreaker,
    ClusterFrontend,
    DeviceTopology,
    EngineConfig,
    OverloadDetector,
    PrecisionConfig,
    Request,
    SamplingParams,
    ServingEngine,
    TenantClass,
)
from repro.launch.runtime import device_report, use_compile_cache
from repro.serving.trace_export import request_traces, write_chrome_trace


def _parse_tenants(spec: str) -> dict:
    """``gold=2:4,bulk=0:1:256:2048`` ->
    ``{name: TenantClass}`` (name=tier:weight[:rate_tokens_s[:burst]])."""
    tenants = {}
    for part in filter(None, spec.split(",")):
        name, _, shape = part.partition("=")
        f = [x for x in shape.split(":")] if shape else []
        tenants[name] = TenantClass(
            name,
            tier=int(f[0]) if len(f) > 0 and f[0] else 0,
            weight=float(f[1]) if len(f) > 1 and f[1] else 1.0,
            rate_tokens_s=float(f[2]) if len(f) > 2 and f[2] else 0.0,
            burst_tokens=float(f[3]) if len(f) > 3 and f[3] else 0.0)
    return tenants


def _engine_config(args) -> EngineConfig:
    return EngineConfig(slots=args.slots, window=args.window,
                        sync_every=args.sync_every,
                        chunk_prefill=args.chunk_prefill,
                        sla_s=args.sla_ms / 1e3,
                        paged=None if not args.no_paged else False,
                        page_size=args.page_size,
                        max_seq=args.max_seq or None,
                        pool_pages=args.pool_pages or None,
                        prefix_cache=args.prefix_cache,
                        preemption=args.preemption,
                        topology=DeviceTopology(dp=args.dp, tp=args.tp),
                        moe_capacity_policy=args.moe_capacity or None,
                        precision=PrecisionConfig(
                            kv_cache_dtype=args.kv_dtype,
                            weight_dtype=args.weight_dtype),
                        tracing=bool(args.trace_out),
                        trace_sample_n=args.trace_sample_n,
                        profile_dir=args.profile_dir or None)


def _build_engine(cfg, params, args):
    return ServingEngine(cfg, params, _engine_config(args))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slots; 0 = derive from the cost model")
    ap.add_argument("--window", type=int, default=256)
    ap.add_argument("--rate", type=float, default=8.0, help="arrivals/s")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--sync-every", type=int, default=8,
                    help="decode ticks per device->host token sync")
    ap.add_argument("--chunk-prefill", type=int, default=64,
                    help="chunked-prefill piece size; 0 = single-shot")
    ap.add_argument("--sla-ms", type=float, default=50.0,
                    help="per-step SLA budget for the admission plan")
    ap.add_argument("--no-paged", action="store_true",
                    help="force rolling-window KV (paged is the default "
                         "for pageable archs)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--max-seq", type=int, default=0,
                    help="per-request token cap / page-table width; "
                         "0 = window (raise to exceed the old window cap)")
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="shared KV pool size in pages; 0 = full headroom, "
                         "less oversubscribes (admission backpressure)")
    ap.add_argument("--kv-dtype", default="", choices=["", "int8"],
                    help="KV-cache page dtype: int8 stores pages as int8 "
                         "values + per-vector fp32 scales (paged only; "
                         "plan_admission converts the saving into slots)")
    ap.add_argument("--weight-dtype", default="", choices=["", "int8"],
                    help="weight-only int8 for the attention/MLP matmuls "
                         "(per-output-channel fp32 scales, f32 accumulation)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="shared-prefix KV cache: keep finished prompts' "
                         "pages in a radix index; later requests alias "
                         "them and prefill only their suffix (paged only)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor/expert-parallel ways per replica (the "
                         "mesh 'model' axis); needs tp*dp local devices — "
                         "on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first")
    ap.add_argument("--dp", type=int, default=1,
                    help="data-parallel ways per replica (the mesh 'data' "
                         "axis)")
    ap.add_argument("--moe-capacity", default="",
                    choices=("", "strict", "backpressure", "drop"),
                    help="MoE capacity-overflow policy; empty = strict on "
                         "sharded MoE replicas, drop otherwise")
    ap.add_argument("--replicas", type=int, default=1,
                    help="ServingEngine replicas behind the cluster "
                         "frontend; 1 = single-engine path")
    ap.add_argument("--route-policy", default="predicted",
                    choices=POLICIES,
                    help="cluster routing policy (with --replicas > 1)")
    ap.add_argument("--ttft-slo-ms", type=float, default=0.0,
                    help="per-request TTFT deadline; 0 = untracked")
    ap.add_argument("--tpot-slo-ms", type=float, default=0.0,
                    help="per-request mean TPOT bound; 0 = untracked")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="decode sampling temperature; 0 = greedy argmax")
    ap.add_argument("--top-k", type=int, default=0,
                    help="sample from the k largest logits; 0 = no cut")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass; 1 = no cut")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed; request i uses seed+i "
                         "(streams reproduce across runs and replicas)")
    ap.add_argument("--request-timeout-s", type=float, default=0.0,
                    help="per-request JCT deadline; overdue requests are "
                         "aborted and their slot/pages freed (0 = none)")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="per-request failover budget at the cluster "
                         "frontend (with --replicas > 1)")
    ap.add_argument("--preemption", action="store_true",
                    help="allow evicting a decoding slot for a more "
                         "urgent arrival; the victim's generated prefix "
                         "is cached and its stream restored bit-identical "
                         "(paged engines only)")
    ap.add_argument("--metrics-out", default="",
                    help="write the merged metrics registry here as "
                         "Prometheus-style text exposition, plus a JSON "
                         "snapshot at PATH.json")
    ap.add_argument("--trace-out", default="",
                    help="turn on request span tracing and write the run "
                         "as Chrome-trace JSON (ui.perfetto.dev)")
    ap.add_argument("--trace-sample-n", type=int, default=1,
                    help="with tracing on, trace only every Nth request "
                         "(rid %% N == 0); 1 = all")
    ap.add_argument("--tenants", default="",
                    help="SLO classes as name=tier:weight[:rate_tokens_s"
                         "[:burst_tokens]],... — requests are tagged "
                         "round-robin; the frontend queue turns "
                         "weighted-fair (DRR across tenants)")
    ap.add_argument("--overload", action="store_true",
                    help="arm the degradation-ladder overload detector "
                         "(uses --ttft-slo-ms as the pooled p99 target) "
                         "and the failover circuit breaker")
    ap.add_argument("--profile-dir", default="",
                    help="arm jax.profiler around the serving loop; the "
                         "XLA trace lands in this dir (TensorBoard)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(device_report(use_compile_cache()), flush=True)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.is_encoder:
        raise SystemExit("encoder-only arch: no autoregressive serving")
    if args.temperature <= 0 and (args.top_k > 0 or args.top_p < 1.0):
        print("warning: --top-k/--top-p have no effect with "
              "--temperature 0 (greedy decode); pass --temperature > 0 "
              "to sample", file=sys.stderr)

    rng = np.random.default_rng(args.seed)
    params = init_params(cfg, jax.random.key(args.seed))
    eng = _build_engine(cfg, params, args)
    if not args.slots:
        print(f"admission plan: slots={eng.slots} "
              f"flush_deadline={eng.plan.flush_deadline_s*1e3:.2f}ms "
              f"(cost-model step={eng.plan.step_latency_s*1e3:.3f}ms)")
    if eng.paged:
        print(f"paged KV: page_size={eng.page_size} max_seq={eng.max_seq} "
              f"pool={eng.pool_pages} pages "
              f"({eng.allocator.capacity} usable + trash)")
    if eng.kv_dtype or args.weight_dtype:
        rep = eng.load_report()
        print(f"quantized: kv_cache_dtype={rep.kv_cache_dtype or 'f32'} "
              f"weight_dtype={rep.weight_dtype or 'f32'} "
              f"kv_bytes/token={rep.kv_bytes_per_token:.0f}")
    if eng.topology.sharded:
        rep = eng.load_report()
        print(f"sharded replica: mesh {dict(eng.topology.mesh_axes)} "
              f"({eng.topology.n_chips} devices), per-axis collective "
              f"s/tick {dict(rep.axis_collective_s)}"
              + (f", moe_capacity_policy={eng.moe_capacity_policy}"
                 if eng.moe_capacity_policy else ""))

    tenants = _parse_tenants(args.tenants)
    if args.overload and not tenants:
        raise SystemExit("--overload needs --tenants: the degradation "
                         "ladder defends SLO tiers")
    cluster = None
    engines = [eng]
    if args.replicas > 1 or tenants:
        # tenants force the cluster path even at 1 replica: the fair
        # queue, admission, and ladder live at the frontend
        engines = [eng] + [_build_engine(cfg, params, args)
                           for _ in range(args.replicas - 1)]
        # cost-model ticks model the target chip, not this host: floor the
        # wall-clock watchdog so a CPU run never trips on modeled speed
        health_s = max(1.0, suggest_health_timeout_s(cfg, slots=eng.slots,
                                                     context=eng.window,
                                                     n_chips=eng.n_chips))
        detector = (OverloadDetector(
            ttft_slo_s=(args.ttft_slo_ms / 1e3) or 1.0)
            if args.overload else None)
        cluster = ClusterFrontend(engines, policy=args.route_policy,
                                  seed=args.seed,
                                  health_timeout_s=health_s,
                                  max_retries=args.max_retries,
                                  tracing=bool(args.trace_out),
                                  tenants=tenants or None,
                                  overload=detector,
                                  breaker=(CircuitBreaker()
                                           if args.overload else None))
        print(f"cluster frontend: {len(engines)} replicas, "
              f"policy={args.route_policy}, "
              f"{'weighted-fair (DRR)' if tenants else 'EDF'} frontend "
              f"queue, health_timeout={health_s*1e3:.0f}ms "
              f"max_retries={args.max_retries}")
        if tenants:
            print("tenants: " + "  ".join(
                f"{tc.name}(tier={tc.tier} w={tc.weight:g}"
                + (f" rate={tc.rate_tokens_s:g}tok/s" if tc.rate_tokens_s
                   else "") + ")" for tc in tenants.values())
                + ("  [overload ladder armed]" if args.overload else ""))

    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    names = list(tenants)
    reqs = [
        Request(
            rid=i,
            tenant=names[i % len(names)] if names else "",
            prompt=rng.integers(0, cfg.vocab_size,
                                args.prompt_len).astype(np.int32),
            max_new_tokens=args.max_new,
            arrival_time=float(arrivals[i]),
            ttft_slo_s=args.ttft_slo_ms / 1e3,
            tpot_slo_s=args.tpot_slo_ms / 1e3,
            timeout_s=args.request_timeout_s,
            sampling=SamplingParams(temperature=args.temperature,
                                    top_k=args.top_k, top_p=args.top_p,
                                    seed=args.sample_seed + i),
        )
        for i in range(args.requests)
    ]
    server = cluster if cluster is not None else eng
    queue = list(reqs)
    t0 = time.time()
    done = 0
    if args.profile_dir:
        for e in engines:
            e.start_profile()
    try:
        while done < args.requests:
            now = time.time() - t0
            while queue and queue[0].arrival_time <= now:
                server.submit(queue.pop(0), now)
            finished = server.step(time.time() - t0)
            done += len(finished)
            if cluster is not None:
                busy = not cluster.idle
            else:
                busy = (eng.n_active or eng.backlog
                        or eng.admission.pending)
            if not busy and queue:
                # idle until the next arrival
                time.sleep(max(0.0,
                               queue[0].arrival_time - (time.time() - t0)))
        done += len(server.drain(time.time() - t0))
    finally:
        if args.profile_dir:
            for e in engines:
                e.stop_profile()
    wall = time.time() - t0
    m = cluster.merged_metrics() if cluster is not None else eng.metrics
    m.total_time = wall
    lats = [r.finish_time - r.arrival_time for r in reqs]
    ttfts = [r.ttft for r in reqs if r.ttft >= 0]
    print(f"served {args.requests} requests in {wall:.2f}s  "
          f"qps={args.requests/wall:.2f}  tok/s={m.total_tokens/wall:.1f}  "
          f"ticks={m.decode_ticks}  host_syncs={m.host_syncs}  "
          f"prefill_chunks={m.prefill_chunks}")
    if m.prefix_hits:
        print(f"prefix cache: {m.prefix_hits} hits, "
              f"{m.prefix_hit_tokens} prompt tokens skipped")
    if m.sampled_requests:
        print(f"sampled decode: {m.sampled_requests} requests "
              f"(T={args.temperature} top_k={args.top_k} "
              f"top_p={args.top_p}, seeds {args.sample_seed}+rid)")
    print(f"latency p50={np.percentile(lats,50)*1e3:.0f}ms "
          f"p99={np.percentile(lats,99)*1e3:.0f}ms  "
          f"mean_jct={np.mean(lats)*1e3:.0f}ms  "
          f"ttft p50={np.percentile(ttfts,50)*1e3:.0f}ms "
          f"p95={np.percentile(ttfts,95)*1e3:.0f}ms")
    if m.slo_tracked:
        print(f"SLO goodput={m.goodput:.3f} "
              f"({m.slo_met}/{m.slo_tracked} in SLO; "
              f"ttft_misses={m.ttft_slo_misses} "
              f"tpot_misses={m.tpot_slo_misses})")
    lifecycle = (m.rejected, m.cancelled, m.timed_out, m.shed, m.failed,
                 m.preempted, m.retried, m.failed_over)
    if any(lifecycle):
        print(f"lifecycle: rejected={m.rejected} cancelled={m.cancelled} "
              f"timed_out={m.timed_out} shed={m.shed} failed={m.failed} "
              f"preempted={m.preempted} (restored={m.preempt_restores}) "
              f"retried={m.retried} failed_over={m.failed_over}")
    if cluster is not None:
        for inst in cluster.instances:
            print(f"  {inst.name}: routed={inst.routed} "
                  f"utilization={inst.utilization:.2f} "
                  f"residual={inst.corrector.correction:+.3f}")
    for name, tm in sorted(m.tenants.items()):
        goodput = (f" goodput={tm.slo_met / tm.slo_tracked:.3f}"
                   if tm.slo_tracked else "")
        print(f"  tenant {name}: admitted={tm.admitted} "
              f"completed={tm.completed} tokens={tm.total_tokens} "
              f"shed={tm.shed} rejected={tm.rejected} "
              f"browned_out={tm.browned_out}"
              f"(-{tm.brownout_trimmed_tokens}tok){goodput}")

    if args.metrics_out:
        reg = (cluster.metrics_registry() if cluster is not None
               else eng.metrics_registry())
        with open(args.metrics_out, "w") as f:
            f.write(reg.exposition())
        with open(args.metrics_out + ".json", "w") as f:
            json.dump(reg.snapshot(), f, indent=2)
        print(f"metrics: {args.metrics_out} (+ .json snapshot)")
    if args.trace_out:
        doc = write_chrome_trace(args.trace_out, request_traces(reqs))
        print(f"trace: {args.trace_out} "
              f"({len(doc['traceEvents'])} events; open in "
              f"https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
