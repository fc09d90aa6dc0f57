"""Smoke test: drive smallNet's main path once on the TPU and check its words.

    python chip_smoke.py             # one chip, phases (a)-(e)
    python chip_smoke.py --chips 4   # the four-chip serving paths only

The model is smallNet at its published width (all 510 parameters), with
the seeded weights frozen in tests/golden/sweep_golden.json as Q16.16
words — so every check below is against words that do not depend on the
device's random-number stream.  Phases on one chip:

  (a) compiled   the interpret default follows the platform, and the
                 fixed_pallas sweep program and engine step lower to Mosaic
                 kernels (`tpu_custom_call`)
  (b) parity     every registered backend against the float reference on
                 params with every leaf perturbed (nonzero biases),
                 fixed vs fixed_pallas word-equal, and each backend
                 drained through a VisionEngine
  (c) stream     16 synthetic 112x112 frames through the StreamingPipeline
                 on the fixed_pallas sweep, whose trunk is one launch:
                 every frame served, window scores and detections identical
                 to the host Tiler's patch-wise `fixed` words, and the first
                 frame's trunk (one-launch and composed) and score words
                 equal to the golden files
  (d) seam512    one 512x512 frame, which the one-launch trunk splits into
                 two tiles: its quad equals the numpy int64 oracle
  (e) serving    a continuously batched fixed_pallas VisionEngine serves
                 256 requests and a ReplicaRouter over ref + fixed_pallas
                 serves 128, with zero sheds and no failover

`--chips 4` runs only the paths that exist across chips: a VisionEngine on
a 4-device serving mesh (scores word-equal to a one-device engine, output
sharded over all four), and a 4-replica fixed_pallas router with one
replica pinned to each chip.

The script runs in one process and starts none.  It prints the device, the
versions, the compile cache, one OK/FAIL line per phase with its compile
and wall seconds (for information, not claims), and as its last line one
JSON object whose "ok" is true only when every phase passed on a TPU.  On
a CPU it runs the same phases through the Pallas interpreter — a rehearsal
— then reports "ok": false and exits 1.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time
import traceback

ROOT = pathlib.Path(__file__).resolve().parent
GOLDEN = ROOT / "tests" / "golden"
STRIDE = 8                      # the sweep's window lattice (golden files)


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _words_equal(a, b, what: str) -> None:
    import numpy as np
    a, b = np.asarray(a, np.int64), np.asarray(b, np.int64)
    _check(a.shape == b.shape, f"{what}: shape {a.shape} != {b.shape}")
    n = int((a != b).sum())
    _check(n == 0, f"{what}: {n}/{a.size} words differ")


def _one_launch_trunk(params, pixels) -> bool:
    """Whether fixed_pallas takes its one-launch trunk for this frame
    (traced, not run)."""
    import jax
    import numpy as np

    from repro.core import backends as B
    be = B.get_backend("fixed_pallas")
    p = be.prepare_params(params)
    frames = np.asarray(pixels[None], np.float32)
    return jax.eval_shape(lambda f: be.frame_trunk(f, p), frames) is not None


def _load_golden():
    from repro.core import smallnet
    sweep = json.loads((GOLDEN / "sweep_golden.json").read_text())
    trunk = json.loads((GOLDEN / "frame_trunk_golden.json").read_text())
    params = smallnet.params_from_words(sweep["inputs"]["params"])
    return params, sweep, trunk


def _engine_ok(stats: dict, n: int, what: str) -> None:
    _check(stats["n"] == n, f"{what}: served {stats['n']} of {n}")
    _check(stats["shed"] == 0, f"{what}: shed {stats['shed_by_reason']}")
    _check(stats["accounted"] and stats["pending"] == 0,
           f"{what}: ledger does not reconcile")


def phase_compiled(ctx) -> str:
    import jax.numpy as jnp

    from repro.core import backends as B
    from repro.core import runtime
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming import fcn_sweep
    from repro.streaming.fcn_sweep import FcnSweep

    on_cpu = ctx["platform"] == "cpu"
    _check(runtime.interpret_default() is on_cpu,
           f"interpret default {runtime.interpret_default()} on "
           f"{ctx['platform']}")
    sweep = FcnSweep(stride=STRIDE)
    fn = fcn_sweep._sweep_fn(B.get_backend("fixed_pallas"), (112, 112),
                             sweep.patch, sweep.stride)
    texts = {"sweep": fn.lower(ctx["params"],
                               jnp.zeros((1, 112, 112, 1))).as_text()}
    eng = VisionEngine(ctx["params"], backend="fixed_pallas", batch_size=32,
                       warmup=False)
    texts["engine step"] = eng._step_fn.lower(
        eng.params, jnp.zeros((32, 28, 28, 1))).as_text()
    for name, text in texts.items():
        _check(("tpu_custom_call" in text) is not on_cpu,
               f"{name}: Mosaic kernel {'present' if on_cpu else 'missing'}")
    return "interpret (CPU)" if on_cpu else "tpu_custom_call in sweep+step"


def phase_parity(ctx) -> str:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backends, smallnet
    from repro.core import fixed_point as fxp
    from repro.serving.vision_engine import VisionEngine

    # every leaf nonzero: init_params' zero biases would hide
    # bias-handling drift
    params = smallnet.seeded_params()
    x = jnp.asarray(_images(8))
    ref = smallnet.apply(params, x, backend="ref")
    plan = smallnet.apply(params, x, backend="plan")
    # (comparison target, max-abs-error bound) per backend
    spec = {"ref": (ref, 0.0), "plan": (plan, 0.0),
            "pallas": (ref, 1e-4), "pallas_plan": (plan, 1e-4),
            "fixed": (plan, 5e-3), "fixed_pallas": (plan, 5e-3),
            "int8": (ref, 0.15)}
    names = backends.list_backends()
    _check(set(spec) == set(names), f"backends {names} != {sorted(spec)}")
    for name in names:
        scores = smallnet.apply(params, x, backend=name)
        if scores.dtype == jnp.int32:
            scores = fxp.from_fixed(scores)
        want, tol = spec[name]
        err = float(jnp.abs(scores - want).max())
        _check(err <= tol, f"{name}: max error {err:.2e} > {tol:g}")
    _words_equal(smallnet.apply(params, x, backend="fixed_pallas"),
                 smallnet.apply(params, x, backend="fixed"),
                 "fixed_pallas vs fixed")
    for name in names:
        eng = VisionEngine(params, backend=name, batch_size=4, warmup=False)
        eng.serve(list(np.asarray(x)))
        _engine_ok(eng.stats(), 8, f"{name} engine")
    return (f"{len(names)} backends within tolerance, fixed == "
            f"fixed_pallas, each engine 8/8")


def phase_stream(ctx) -> str:
    import numpy as np

    from repro.core import fixed_point as fxp
    from repro.serving.vision_engine import VisionEngine
    from repro.streaming.fcn_sweep import (FcnSweep, composed_feature_maps,
                                           sweep_feature_maps)
    from repro.streaming.pipeline import StreamingPipeline
    from repro.streaming.sources import SyntheticVideoSource
    from repro.streaming.tiler import Tiler

    params = ctx["params"]
    source = SyntheticVideoSource(n_frames=16, seed=7)
    frames = source.frames()
    _words_equal(fxp.to_fixed(frames[0].pixels[..., 0]),
                 ctx["sweep_golden"]["inputs"]["frame"],
                 "frame 0 vs golden frame")
    _check(_one_launch_trunk(params, frames[0].pixels),
           "the fixed_pallas sweep has no one-launch trunk at 112x112")
    # detection threshold: the 80th percentile of frame 0's confidences, so
    # the clip has real detections to compare
    patchwise = Tiler(stride=STRIDE)
    tiles, _ = patchwise.extract(frames[0])
    conf = patchwise._confidences(
        patchwise.score(params, tiles, backend="fixed"))
    thr = float(np.quantile(conf.max(-1), 0.8))
    patchwise = Tiler(stride=STRIDE, threshold=thr)
    sweep = FcnSweep(stride=STRIDE, threshold=thr)

    eng = VisionEngine(params, backend="fixed_pallas", warmup=False)
    pipe = StreamingPipeline(source, eng, sweep)         # unpaced
    results = sorted(pipe.run(), key=lambda r: r.index)
    st = pipe.stats()
    _check(st["frames_in"] == 16 and st["frames_served"] == 16
           and st["frames_dropped"] == 0 and st["accounted"],
           f"pipeline served {st['frames_served']}/{st['frames_in']}, "
           f"drops {st['drops_by_stage']}")

    n_det = 0
    for f, r in zip(frames, results):
        fb, _ = sweep.extract(f)
        tiles, _ = patchwise.extract(f)
        _words_equal(sweep.score(params, fb, backend="fixed_pallas"),
                     patchwise.score(params, tiles, backend="fixed"),
                     f"frame {f.index} window scores")
        want = patchwise.detect(params, f, backend="fixed")
        _check(r.detections == want, f"frame {f.index} detections differ")
        n_det += len(want)
    _check(n_det > 0, "the clip produced no detections")

    for trunk, maps_of in (("one-launch", sweep_feature_maps),
                           ("composed", composed_feature_maps)):
        maps = maps_of(params, frames[0].pixels, backend="fixed_pallas")
        for name, words in maps.items():
            _words_equal(words, ctx["trunk_golden"]["maps"]["q16_16"][name],
                         f"{trunk} trunk {name} vs frame_trunk_golden")
            _words_equal(words, ctx["sweep_golden"]["maps"][name],
                         f"{trunk} trunk {name} vs sweep_golden")
    fb, _ = sweep.extract(frames[0])
    _words_equal(sweep.score(params, fb, backend="fixed_pallas"),
                 ctx["sweep_golden"]["scores"], "window scores vs golden")
    return (f"16/16 frames, {n_det} detections, scores word-exact, "
            f"golden words equal")


def phase_seam512(ctx) -> str:
    import numpy as np

    from repro.core import backends as B
    from repro.kernels.frame_trunk import choose_tile
    from repro.kernels.frame_trunk.ref import frame_trunk_quad_ref
    from repro.streaming.fcn_sweep import sweep_feature_maps
    from repro.streaming.sources import SyntheticVideoSource

    tile = choose_tile(512, 512)
    _check(tile != (512, 512), "512x512 must split into several tiles")
    frame = SyntheticVideoSource(n_frames=1, frame_shape=(512, 512),
                                 seed=7).frames()[0]
    be = B.get_backend("fixed_pallas")
    _check(_one_launch_trunk(ctx["params"], frame.pixels),
           "the fixed_pallas sweep has no one-launch trunk at 512x512")
    maps = sweep_feature_maps(ctx["params"], frame.pixels, backend=be)
    p = be.prepare_params(ctx["params"])
    x = np.asarray(be.ingest(frame.pixels[None]))[0]
    oracle = frame_trunk_quad_ref(
        x, np.asarray(p["conv1"]["w"]), np.asarray(p["conv1"]["b"]),
        np.asarray(p["conv2"]["w"]), np.asarray(p["conv2"]["b"]), be.cfg)
    for k, (name, words) in enumerate(maps.items()):
        _words_equal(words, oracle[k], f"512 {name} vs numpy oracle")
    return f"tile {tile[0]}x{tile[1]}, quad == numpy oracle"


def _images(n: int):
    from repro.data import synth_mnist
    return synth_mnist.make_dataset(n, seed=1)[0]


def phase_serving(ctx) -> str:
    import numpy as np

    from repro.core import smallnet
    from repro.serving.router import ReplicaRouter
    from repro.serving.vision_engine import VisionEngine

    params = ctx["params"]
    images = _images(256)
    want = np.asarray(smallnet.apply(params, images, backend="fixed"))

    eng = VisionEngine(params, backend="fixed_pallas", batch_size=32)
    eng.start()
    try:
        res = eng.serve(list(images))
    finally:
        eng.stop()
    _engine_ok(eng.stats(), 256, "engine")
    _check(eng.fault is None, f"engine faulted: {eng.fault!r}")
    _words_equal(np.stack([r.scores for r in res]), want,
                 "engine scores vs fixed")

    router = ReplicaRouter.from_backends(params, ["ref", "fixed_pallas"],
                                         batch_size=32)
    rres = router.serve(list(images[:128]))
    rs = router.stats()
    _engine_ok(rs, 128, "router")
    _check(not rs["failed"], f"router failed over from {rs['failed']}")
    fp = [i for i, r in enumerate(rres) if r.replica == 1]
    _words_equal(np.stack([rres[i].scores for i in fp]), want[fp],
                 "router fixed_pallas scores vs fixed")
    return (f"engine 256/256, router 128/128 (served_by "
            f"{rs['served_by']}), 0 sheds")


def phase_mesh4(ctx) -> str:
    import jax
    import numpy as np

    from repro.launch.mesh import make_serving_mesh
    from repro.serving.vision_engine import VisionEngine

    params, images = ctx["params"], _images(128)
    eng4 = VisionEngine(params, backend="fixed_pallas", batch_size=128,
                        mesh=make_serving_mesh(4))
    eng1 = VisionEngine(params, backend="fixed_pallas", batch_size=128)
    r4, r1 = eng4.serve(list(images)), eng1.serve(list(images))
    _engine_ok(eng4.stats(), 128, "4-device engine")
    _engine_ok(eng1.stats(), 128, "1-device engine")
    _words_equal(np.stack([r.scores for r in r4]),
                 np.stack([r.scores for r in r1]), "4-device vs 1-device")
    out = eng4._step_fn(eng4.params,
                        jax.device_put(np.asarray(images), eng4._in_sharding))
    n_dev = len(out.sharding.device_set)
    _check(n_dev == 4, f"engine output spans {n_dev} devices, not 4")
    return "128/128, words equal to 1 device, output on 4 devices"


def phase_router4(ctx) -> str:
    import jax
    import numpy as np

    from repro.core import smallnet
    from repro.launch.mesh import make_serving_mesh
    from repro.serving.router import ReplicaRouter
    from repro.serving.vision_engine import VisionEngine

    params, images = ctx["params"], _images(256)
    devs = jax.devices()[:4]
    router = ReplicaRouter([
        VisionEngine(params, backend="fixed_pallas", batch_size=32,
                     mesh=make_serving_mesh(devices=[d])) for d in devs])
    res = router.serve(list(images))
    rs = router.stats()
    _engine_ok(rs, 256, "router")
    _check(not rs["failed"], f"router failed over from {rs['failed']}")
    _check(all(rs["served_by"][i] > 0 for i in range(4)),
           f"some chip served nothing: {rs['served_by']}")
    for i, (eng, d) in enumerate(zip(router.replicas, devs)):
        leaf = jax.tree_util.tree_leaves(eng.params)[0]
        _check(leaf.devices() == {d}, f"replica {i} params not on {d}")
    want = np.asarray(smallnet.apply(params, images, backend="fixed"))
    _words_equal(np.stack([r.scores for r in res]), want,
                 "router scores vs fixed")
    return f"256/256 across 4 chips (served_by {rs['served_by']})"


ONE_CHIP = (("a compiled", phase_compiled), ("b parity", phase_parity),
            ("c stream", phase_stream), ("d seam512", phase_seam512),
            ("e serving", phase_serving))
FOUR_CHIPS = (("mesh4 engine", phase_mesh4), ("router4", phase_router4))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip; 4: only the "
                         "four-chip mesh engine and pinned-replica router")
    args = ap.parse_args()

    sys.path.insert(0, str(ROOT / "src"))
    import importlib.metadata

    import jax
    import jaxlib

    from repro.core import runtime

    cache = runtime.init_compile_cache()
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"device: platform={platform} kind={kind} count={len(devs)}")
    print(f"versions: jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={libtpu}")
    print(f"compile cache: {cache}")
    if len(devs) < args.chips:
        print(f"FAIL --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devs)}")
        return 1

    compile_s = [0.0]

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[0] += duration

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    params, sweep_golden, trunk_golden = _load_golden()
    ctx = {"platform": platform, "params": params,
           "sweep_golden": sweep_golden, "trunk_golden": trunk_golden}
    ok = True
    for name, phase in (ONE_CHIP if args.chips == 1 else FOUR_CHIPS):
        compile_s[0] = 0.0
        t0 = time.perf_counter()
        try:
            detail, status = phase(ctx), "OK"
        except Exception as e:          # noqa: BLE001 — report every phase
            traceback.print_exc()
            detail, status = f"{type(e).__name__}: {e}", "FAIL"
            ok = False
        print(f"{status} phase {name}: {detail} "
              f"[compile {compile_s[0]:.1f} s, wall "
              f"{time.perf_counter() - t0:.1f} s]", flush=True)

    if platform != "tpu":
        print(f"no TPU ({platform}): this run is a rehearsal, not a chip "
              f"result")
        ok = False
    print(json.dumps({"ok": ok, "device": {"platform": platform,
                                           "kind": kind,
                                           "count": len(devs)}}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
