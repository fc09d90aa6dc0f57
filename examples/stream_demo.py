"""Streaming demo: a live synthetic clip, end to end, at frame rate.

Trains a small float smallNet (enough for confident digit scores), then
streams a 50-frame synthetic video — digits drifting and scaling over a
112x112 canvas — through the real-time pipeline: paced source -> sliding-
window tiler -> batched engine waves on the chosen backend -> thresholded,
deduplicated detections.  Prints sustained FPS, latency percentiles, drop
accounting, and the per-frame detections vs. ground truth.

With `--sweep` the sliding-window host tiler is swapped for the
fully-convolutional frame sweep (`streaming/fcn_sweep.FcnSweep`): the conv
trunk runs ONCE per frame on device and every window is scored from the
pooled feature map — identical detections (word-exact on the fixed
substrates), finer stride, no host patch extraction.

With `--trace` the run records per-frame spans (`repro/obs`): after the
clip, the first few frames are printed as ASCII waterfalls — frame root,
tile/infer/aggregate stages, engine queue-wait and device-step — and the
whole flight-recorder ring is dumped to `stream_demo_trace.jsonl`.

    PYTHONPATH=src python examples/stream_demo.py [--backend fixed_pallas]
        [--frames 50] [--fps 10] [--no-train] [--sweep] [--trace]
"""
import argparse

import jax

from repro.core import backends, deploy, runtime, smallnet
from repro.obs import recorder as obs_recorder
from repro.obs import trace as obs_trace
from repro.serving.vision_engine import VisionEngine
from repro.streaming.fcn_sweep import FcnSweep
from repro.streaming.pipeline import StreamConfig, StreamingPipeline
from repro.streaming.sources import PacedPlayer, SyntheticVideoSource
from repro.streaming.tiler import Tiler


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="fixed_pallas",
                    choices=backends.list_backends())
    ap.add_argument("--frames", type=int, default=50)
    ap.add_argument("--fps", type=float, default=10.0)
    ap.add_argument("--stride", type=int, default=None,
                    help="window stride (default: 14 for the host tiler, "
                         "8 for --sweep; sweep strides must be multiples "
                         "of 4)")
    ap.add_argument("--sweep", action="store_true",
                    help="score windows from one full-frame conv sweep on "
                         "device instead of host-extracted patches")
    ap.add_argument("--threshold", type=float, default=0.9)
    ap.add_argument("--min-mass", type=float, default=0.04,
                    help="foreground gate: skip windows whose mean pixel "
                         "intensity is below this (the net never trained "
                         "on empty background)")
    ap.add_argument("--no-train", action="store_true",
                    help="skip training (random weights; detections are "
                         "arbitrary but the pipeline mechanics are real)")
    ap.add_argument("--trace", action="store_true",
                    help="record per-frame spans; print waterfalls for the "
                         "first frames and dump stream_demo_trace.jsonl")
    ap.add_argument("--trace-dir", default=".",
                    help="directory for the --trace dump")
    args = ap.parse_args()
    runtime.init_compile_cache()

    tracer = None
    if args.trace:
        tracer = obs_trace.enable(capacity=1 << 17,
                                  dump_dir=args.trace_dir)

    if args.no_train:
        params = smallnet.init_params(jax.random.key(0))
    else:
        print("== train float smallNet (quick run) ==")
        res = deploy.train_smallnet(n_train=3000, n_test=500, epochs=8)
        print(f"   test_acc={res.test_acc:.4f}")
        params = res.params

    mode = "FCN sweep" if args.sweep else "host tiler"
    print(f"== stream {args.frames} frames at {args.fps:g} FPS "
          f"through backend={args.backend!r} ({mode}) ==")
    source = SyntheticVideoSource(n_frames=args.frames, seed=7)
    if args.sweep:
        tiler = FcnSweep(stride=args.stride or 8, threshold=args.threshold,
                         min_mass=args.min_mass)
    else:
        tiler = Tiler(stride=args.stride or 14, threshold=args.threshold,
                      min_mass=args.min_mass)
    # in sweep mode the engine only carries params/backend — skip compiling
    # the batched 28x28 step it would never run (the pipeline warms the
    # whole-frame sweep program itself)
    engine = VisionEngine(params, backend=args.backend, batch_size=64,
                          warmup=not args.sweep)
    pipe = StreamingPipeline(
        PacedPlayer(source, fps=args.fps), engine, tiler,
        config=StreamConfig(deadline_ms=3e3 / args.fps, queue_size=4))
    results = pipe.run()

    truth = {f.index: f.truth for f in source}
    for r in results[:10]:
        dets = ", ".join(f"{d.label}@({d.y},{d.x}) p={d.score:.2f}"
                         for d in r.detections) or "-"
        gt = ", ".join(f"{b.label}@({b.y},{b.x})" for b in truth[r.index])
        print(f"   frame {r.index:3d}  {r.latency_s*1e3:6.1f} ms  "
              f"det=[{dets}]  truth=[{gt}]")
    if len(results) > 10:
        print(f"   ... {len(results) - 10} more frames")

    s = pipe.stats()
    print("== stats ==")
    print(f"   sustained_fps={s['sustained_fps']:.1f} (target {args.fps:g})  "
          f"served={s['frames_served']}/{s['frames_in']}  "
          f"dropped={s['frames_dropped']} {s['drops_by_reason'] or ''}")
    print(f"   latency p50={s.get('latency_p50_ms', 0):.1f}ms "
          f"p99={s.get('latency_p99_ms', 0):.1f}ms  "
          f"batch_occupancy={s.get('batch_occupancy', 0):.2f}  "
          f"detections={s['detections_total']}")
    print(f"   accounted={'OK' if s['accounted'] else 'LOST FRAMES'} "
          f"(in == served + dropped)")

    if tracer is not None:
        import os
        spans = tracer.recorder.spans()
        print("== trace waterfalls (first 3 frames) ==")
        trace_ids = []
        for sp in spans:                      # keep first-seen frame order
            if sp.name == "frame" and sp.trace_id not in trace_ids:
                trace_ids.append(sp.trace_id)
        for tid in trace_ids[:3]:
            print(obs_recorder.waterfall(spans, tid, max_spans=24))
        path = tracer.recorder.dump_jsonl(
            os.path.join(args.trace_dir, "stream_demo_trace.jsonl"),
            reason="stream_demo",
            detail=f"frames={args.frames} backend={args.backend}")
        print(f"== trace dumped: {path} ({len(spans)} spans, "
              f"{tracer.recorder.evicted} evicted) ==")
        obs_trace.disable()


if __name__ == "__main__":
    main()
