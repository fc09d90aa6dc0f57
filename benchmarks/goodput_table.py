"""Goodput under a latency SLO, per serving topology, per arrival process.

FPS on a paced clip says nothing about overload: the north-star question is
how much *useful* work the stack completes when requests arrive on the
users' clock — goodput = fraction of SUBMITTED requests answered within
their deadline (sheds and late answers both count against it).

For each serving topology this table:

  1. calibrates the topology's service capacity (drain a full backlog,
     read the busy-time service rate — idle never deflates it),
  2. replays seeded open-loop arrival schedules (`streaming/loadgen.py`:
     Poisson / bursty / diurnal) at offered loads of 0.5x and 2.0x that
     capacity — same request count per row, so wall time is load-invariant,
  3. reports goodput, shed counts per reason, latency percentiles, and the
     accounting invariant `submitted == served + shed` per row.

Topologies: a single continuous-batching `VisionEngine` on the float ref
and fused fixed-point Pallas substrates (admission bound `max_queue`,
per-request deadlines), a 2-replica `ReplicaRouter` under the SLO-aware
policy (projected-wait dispatch, door shedding), and the disaggregated
trunk/head fleet (`serving/disagg.DisaggServer`, `disagg_fixed`): trunk
and head pools with independent replica counts and service floors joined
by the feature-map cache, replaying 112x112 frame queries over a
cache-hot pool — its rows land next to the batched engines' so trunk vs
head scaling shows up in the same goodput columns.

Each row also reports `mfu_load` — MFU under load: the busy-time served
rate times the deployed per-image model FLOPs (analysis/mfu.py), over the
resolved device's peak at the backend's dtype class.  It answers "how much
of the machine does the serving discipline actually keep busy" and is the
load-side twin of the perf ledger's per-route `mfu` column.

`--smoke` is the CI gate (Poisson + bursty):
  - every row's ledger reconciles (engine AND fleet level),
  - the 2.0x rows shed (overload must engage admission control — a queue
    that never sheds is an unbounded queue),
  - queue high-water stays within the admission bound,
  - goodput is monotone in offered-load headroom (0.5x >= 2.0x per
    topology/process).

    PYTHONPATH=src python -m benchmarks.goodput_table --smoke
    PYTHONPATH=src python -m benchmarks.goodput_table --full   # + diurnal
    PYTHONPATH=src python -m benchmarks.goodput_table --smoke --trace

`--trace` records request/batch spans for every row (`repro/obs`) and
dumps `goodput_trace.jsonl` + `goodput_metrics.prom` under `--trace-dir`
— the serving-side observability artifacts next to the stream table's.
"""
from __future__ import annotations

import argparse
import sys


def _params():
    from benchmarks.stream_table import _params as p
    return p()


SEED = 7
BATCH = 32
QUEUE_BOUND = 4          # max_queue = QUEUE_BOUND * batch_size
FLOOR_MS = 10.0          # per-step service-time floor: a deterministic rate
                         # limiter (capacity ~= batch/floor) so the open-loop
                         # rows measure serving DISCIPLINE, not host speed —
                         # on real hardware run with --floor-ms 0
LOADS = {"0.5x": 0.5, "2.0x": 2.0}
SMOKE_PROCESSES = ("poisson", "bursty")
# dtype class whose device peak the MFU-under-load column divides by.
# The disagg topology is deliberately absent: its feature-map cache skips
# trunk FLOPs on hits, so served-qps x full-model-FLOPs is not the work
# the device actually did and the MFU identity would overstate it.
TOPO_BACKEND = {"engine_ref": "ref", "engine_fixed_pallas": "fixed_pallas",
                "router_slo_x2": "ref"}
# disagg_fixed fleet shape: trunk/head replica counts scale independently
DISAGG_TRUNKS = 2
DISAGG_HEADS = 2
DISAGG_FRAMES = 32       # distinct 112x112 frames in the query pool; uids
                         # cycle over them, so steady state is cache-hot


def _mfu_under_load(topo: str, stats: dict) -> float | None:
    """Busy-time served qps x deployed per-image model FLOPs / device peak.
    None when the row carries no throughput (nothing served) or the
    topology has no single FLOPs-per-query identity (disagg + cache)."""
    from repro.analysis import mfu

    qps = stats.get("throughput_qps")
    backend = TOPO_BACKEND.get(topo)
    if not qps or backend is None:
        return None
    device, _ = mfu.resolve()
    dtype, word_bytes = mfu.backend_numerics(backend)
    flops = mfu.deployed_workload(word_bytes).flops
    return qps * flops / device.peak(dtype)


def _deadline_ms(capacity_qps: float, batch: int) -> float:
    """SLO for a topology: ~6 batch-service-times (comfortable at half
    load, hopeless for a 2x backlog), floored so scheduler jitter on a
    fast machine can't dominate."""
    return max(25.0, 6.0 * batch / capacity_qps * 1e3)


def _calibrate_engine(params, backend: str, batch: int,
                      floor_s: float) -> float:
    """Busy-time service rate (qps) of one engine draining a full backlog
    of 8 batches — the capacity the offered loads are scaled against.
    With a service floor this converges to batch/floor_s by construction."""
    import numpy as np

    from repro.serving.vision_engine import VisionEngine

    eng = VisionEngine(params, backend=backend, batch_size=batch,
                      min_step_s=floor_s)
    imgs = np.zeros((8 * batch, 28, 28, 1), np.float32)
    eng.submit_many(imgs)
    eng.run()
    rate = eng.service_rate_qps()
    assert rate is not None and rate > 0
    return rate


def _run_engine_row(params, backend: str, gen, images, slo_ms: float,
                    floor_s: float) -> dict:
    from repro.serving.vision_engine import VisionEngine

    eng = VisionEngine(params, backend=backend, batch_size=BATCH,
                       max_queue=QUEUE_BOUND * BATCH, min_step_s=floor_s)
    eng.start()
    try:
        gen.replay(lambda a, t: eng.submit(images[a.uid], deadline_ms=slo_ms,
                                           t_submit=t))
    finally:
        eng.stop(drain=True)
    s = eng.stats()
    s["queue_bound"] = QUEUE_BOUND * BATCH
    return s


def _mk_disagg(params, floor_s: float, max_queue: int | None):
    """The disagg_fixed fleet: trunk replicas carry the heavy-stage floor,
    head replicas a quarter of it (the paper's stage asymmetry), so with a
    cache-hot pool the heads are the serialization point and capacity is
    ~DISAGG_HEADS / (floor_s / 4) by construction."""
    from repro.serving.disagg import DisaggServer

    return DisaggServer(params, backend="fixed",
                        n_trunk=DISAGG_TRUNKS, n_head=DISAGG_HEADS,
                        trunk_floor_s=floor_s, head_floor_s=floor_s / 4,
                        cache_capacity=DISAGG_FRAMES + 4,
                        max_queue=max_queue,
                        n_workers=DISAGG_TRUNKS + DISAGG_HEADS)


def _disagg_frames(params):
    """The disagg query pool: DISAGG_FRAMES distinct seeded 112x112 frames
    (the server's native geometry — LoadGen's 28x28 images are the batched
    engines' shape, not a frame)."""
    from repro.streaming.sources import SyntheticVideoSource

    src = SyntheticVideoSource(n_frames=DISAGG_FRAMES, seed=SEED)
    return [f.pixels for f in src.frames()]


def _calibrate_disagg(params, frame_px, floor_s: float) -> float:
    """Drain 8 passes over the query pool through a fresh fleet and read
    the served rate — the engine-calibration idiom for the disagg server
    (the first pass pays the trunk misses; the other seven amortize them
    into the cache-hot steady state the replay rows actually run in)."""
    srv = _mk_disagg(params, floor_s, max_queue=None)
    srv.start()
    try:
        uids = [srv.submit(px) for px in frame_px * 8]
        srv.wait(uids)
    finally:
        srv.stop(drain=True)
    s = srv.stats()
    assert s["accounted"] and s["n"] == len(frame_px) * 8
    return s["n"] / s["wall_s"]


def _run_disagg_row(params, gen, frame_px, slo_ms: float,
                    floor_s: float) -> dict:
    srv = _mk_disagg(params, floor_s, max_queue=QUEUE_BOUND * BATCH)
    srv.start()
    try:
        gen.replay(lambda a, t: srv.submit(
            frame_px[a.uid % len(frame_px)], deadline_ms=slo_ms,
            t_submit=t))
    finally:
        srv.stop(drain=True)
    s = srv.stats()
    s["queue_bound"] = QUEUE_BOUND * BATCH
    return s


def _run_router_row(params, gen, images, slo_ms: float,
                    floor_s: float) -> dict:
    from repro.serving.router import ReplicaRouter

    router = ReplicaRouter.from_backends(
        params, ["ref", "ref"], batch_size=BATCH // 2, policy="slo",
        slo_ms=slo_ms, engine_kw={"max_queue": QUEUE_BOUND * BATCH,
                                  "min_step_s": floor_s})
    router.start()
    try:
        gen.replay(lambda a, t: router.submit(images[a.uid], t_submit=t))
    finally:
        router.stop(drain=True)
    s = router.stats()
    s["queue_bound"] = QUEUE_BOUND * BATCH
    return s


def measure(*, processes, n_requests: int, topologies=None,
            floor_s: float = FLOOR_MS / 1e3) -> list[dict]:
    """All (topology, process, load) rows.  Per row: a fresh engine/fleet,
    a seeded open-loop replay, and the stats ledger."""
    from repro.streaming.loadgen import LoadGen

    params = _params()
    topo_caps = {}
    topo_caps["engine_ref"] = _calibrate_engine(params, "ref", BATCH,
                                                floor_s)
    topo_caps["engine_fixed_pallas"] = _calibrate_engine(
        params, "fixed_pallas", BATCH, floor_s)
    # 2 replicas at half batch each: fleet capacity ~= one full-batch engine
    topo_caps["router_slo_x2"] = 2 * _calibrate_engine(params, "ref",
                                                       BATCH // 2, floor_s)
    frame_px = _disagg_frames(params)
    topo_caps["disagg_fixed"] = _calibrate_disagg(params, frame_px, floor_s)
    if topologies is not None:
        topo_caps = {k: v for k, v in topo_caps.items() if k in topologies}

    rows = []
    for topo, cap in topo_caps.items():
        slo_ms = _deadline_ms(cap, BATCH)
        for process in processes:
            for load_name, factor in LOADS.items():
                rate = factor * cap
                gen = LoadGen(process=process, rate_qps=rate,
                              n_requests=n_requests, n_streams=4, seed=SEED)
                if topo == "disagg_fixed":
                    s = _run_disagg_row(params, gen, frame_px, slo_ms,
                                        floor_s)
                elif topo == "router_slo_x2":
                    images = gen.images()  # render off the serving clock
                    s = _run_router_row(params, gen, images, slo_ms, floor_s)
                elif topo.startswith("engine_"):
                    images = gen.images()
                    s = _run_engine_row(params, topo[len("engine_"):],
                                        gen, images, slo_ms, floor_s)
                else:
                    raise ValueError(topo)
                rows.append({
                    "topology": topo, "process": process, "load": load_name,
                    "capacity_qps": cap, "offered_qps": gen.offered_qps,
                    "slo_ms": slo_ms, "stats": s,
                    "mfu_under_load": _mfu_under_load(topo, s),
                })
    return rows


def gate(rows: list[dict]) -> list[str]:
    """The --smoke CI conditions over a measured row set."""
    failures = []
    goodput = {}
    for r in rows:
        s = r["stats"]
        tag = f"{r['topology']}/{r['process']}/{r['load']}"
        if not s["accounted"]:
            failures.append(
                f"{tag}: ledger does not reconcile: submitted="
                f"{s['submitted']} served={s['n']} shed={s['shed']} "
                f"pending={s['pending']}")
        for rep in s.get("per_replica", []):
            if not rep["accounted"]:
                failures.append(f"{tag}: replica-level ledger does not "
                                f"reconcile: {rep['shed_by_reason']}")
        for name, st in s.get("per_stage", {}).items():
            if not st["accounted"]:
                failures.append(f"{tag}: stage '{name}' ledger does not "
                                f"reconcile: {st['shed_by_reason']}")
        if "goodput" not in s:
            failures.append(f"{tag}: no goodput reported")
            continue
        goodput[(r["topology"], r["process"], r["load"])] = s["goodput"]
        hwm = s.get("queue_hwm", 0)
        if isinstance(hwm, (int, float)) and hwm > s["queue_bound"]:
            failures.append(f"{tag}: queue high-water {hwm} exceeded the "
                            f"admission bound {s['queue_bound']}")
        if r["load"] == "2.0x" and s["shed"] == 0:
            failures.append(
                f"{tag}: no shedding under 2x-capacity offered load — "
                f"admission control never engaged (unbounded queue?)")
        mfu_load = r.get("mfu_under_load")
        if mfu_load is not None and not 0.0 < mfu_load <= 1.0:
            failures.append(
                f"{tag}: mfu_under_load={mfu_load:.3e} outside (0, 1] — "
                f"served-rate or device-peak accounting broke")
    for (topo, proc, load), g_hi in goodput.items():
        if load != "2.0x":
            continue
        g_lo = goodput.get((topo, proc, "0.5x"))
        if g_lo is not None and g_lo < g_hi:
            failures.append(
                f"{topo}/{proc}: goodput not monotone in headroom: "
                f"0.5x={g_lo:.3f} < 2.0x={g_hi:.3f}")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small row set + CI gates (nonzero exit on fail)")
    ap.add_argument("--full", action="store_true",
                    help="all three arrival processes, bigger schedules")
    ap.add_argument("--requests", type=int, default=None,
                    help="arrivals per row (default: 1500 smoke / 4000 full)")
    ap.add_argument("--floor-ms", type=float, default=FLOOR_MS,
                    help="per-step service floor; 0 = raw hardware capacity")
    ap.add_argument("--trace", action="store_true",
                    help="record request/batch spans for every row and dump "
                         "goodput_trace.jsonl + goodput_metrics.prom under "
                         "--trace-dir")
    ap.add_argument("--trace-dir", default="traces",
                    help="directory for --trace artifacts")
    args = ap.parse_args()
    from repro.core import runtime
    runtime.init_compile_cache()

    tracer = None
    if args.trace:
        from repro.obs import trace as T
        # every row's requests land in one ring (capacity sized for the
        # full smoke row set: ~18k requests x 2 spans + batch spans)
        tracer = T.enable(capacity=1 << 18, dump_dir=args.trace_dir)

    from repro.streaming.loadgen import PROCESSES
    processes = PROCESSES if args.full else SMOKE_PROCESSES
    n = args.requests or (4000 if args.full else 1500)
    rows = measure(processes=processes, n_requests=n,
                   floor_s=args.floor_ms / 1e3)

    print("name,us_per_call,derived")
    for r in rows:
        s = r["stats"]
        mfu_load = r.get("mfu_under_load")
        mfu_s = f"{mfu_load:.3e}" if mfu_load is not None else "n/a"
        print(f"goodput/{r['topology']}_{r['process']}_{r['load']},,"
              f"goodput={s.get('goodput', 0.0):.3f} "
              f"submitted={s['submitted']} served={s['n']} shed={s['shed']} "
              f"offered_qps={r['offered_qps']:.0f} "
              f"capacity_qps={r['capacity_qps']:.0f} "
              f"slo_ms={r['slo_ms']:.1f} "
              f"p99_ms={s.get('latency_p99_ms', 0.0):.2f} "
              f"mfu_load={mfu_s} "
              f"shed_by={s['shed_by_reason']}")

    if tracer is not None:
        import os

        from repro.obs import recorder as R
        from repro.obs import trace as T
        jsonl = tracer.recorder.dump_jsonl(
            os.path.join(args.trace_dir, "goodput_trace.jsonl"),
            reason="goodput_table",
            detail=f"requests={n} processes={','.join(processes)}")
        prom = R.dump_prometheus(
            os.path.join(args.trace_dir, "goodput_metrics.prom"))
        print(f"goodput/trace_artifacts,,jsonl={jsonl} prom={prom} "
              f"spans={len(tracer.recorder)} "
              f"evicted={tracer.recorder.evicted}")
        T.disable()

    failures = gate(rows) if args.smoke else []
    for f in failures:
        print(f"goodput/FAIL,,{f}")
    print(f"goodput/result,,{'FAIL' if failures else 'OK'}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
