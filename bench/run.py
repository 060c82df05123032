#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is read from ``BENCHMARK.json`` at the checkout root.  Its
pieces are found by name: the configuration's sizes
(``bench/configs/<config>.json``) and code (``bench/configs/<config>.py``:
``build``, ``init``, ``reference``), the traffic mix
(``bench/traffic/<traffic>.json``, read by ``bench/loadgen.py``), one
reader per metric (``bench/metrics/<metric>.py``) and the peaks of the
device (``bench/peaks.json``).

A run: build the model and its weights from the seed on the device,
make a pool of images per resolution of the mix on the host, build
``AsyncServeFrontend`` with the mix's buckets, compile and warm every
bucket program (set-up), serve the mix for ``--seconds`` seconds (the
window), then compare a sample of the served logits, drawn from the
seed, with the configuration's plain reference.  The cell's end-to-end
metrics are read from the window; ``--trace 1`` profiles the window
and reads the cell's per-layer metrics instead.  The last stdout line is one
JSON object; the compared numbers, each beside its limit, are the last
stderr lines and the result's last key.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result; a ``device_kind`` missing from ``peaks.json``
exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
#: plan/autotune caches of the program, kept inside the checkout so no
#: tuning state from elsewhere is read (gitignored by bench/.gitignore)
STATE = BENCH / ".state"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
#: seconds of the mix served after warm-up and before the window, so the
#: host paths are warm too
WARM_SECONDS = 1.0
#: images per call of the reference
REF_BLOCK = 8


class NoChip(Exception):
    pass


def setup_env() -> None:
    """Point the program's caches inside the checkout and put the
    program (``src``) and the benchmark on the path."""
    os.environ["REPRO_CACHE_DIR"] = str(STATE / "repro")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_benchmark() -> Dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def find_cell(bench: Dict, name: str):
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return cell, config


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_device(chips: int) -> Dict:
    """The device as JAX reports it; refuses anything but enough TPUs."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found platform {dev.platform!r}, "
                     f"device_kind {dev.device_kind!r}, {len(devs)} device(s)")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)} "
                     f"{dev.device_kind!r} device(s)")
    return info


def load_peaks(kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def _seeds(seed: int):
    """Independent streams for weights, images, traffic and the check."""
    import numpy as np
    return np.random.SeedSequence(int(seed)).spawn(4)


class CompileCounter:
    """Counts the executables JAX builds or loads from its cache
    (``n``), and those it had to compile anew (``misses``), while the
    ``with`` block runs."""

    def __init__(self):
        self.n = 0
        self.misses = 0

    def _on(self, event, duration, **kw):
        if event == COMPILE_EVENT:
            self.n += 1

    def _on_miss(self, event, **kw):
        if event == CACHE_MISS_EVENT:
            self.misses += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_miss)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_miss)


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's fixed path in the
    checkout, every executable kept, so only a checkout's first run
    compiles."""
    import jax
    from repro.launch import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


@dataclasses.dataclass
class Window:
    """What a metric reader gets (times on the loop's clock)."""
    seconds: float
    t0: float                   # the window's start
    setup_s: float
    sent: list                  # loadgen.Sent of the window
    batches: list               # BatchTrace dispatched from the window on
    requests: list              # RequestTrace of the window's requests
    trace: Optional[object]     # trace.Summary, or None
    work: Dict[tuple, list]     # (geometry, bucket) -> [work.NodeWork]
    peaks: Dict
    t_end: float = math.nan     # every request of the window back


class HostSpans:
    """The serve loop's host spans ``(name, start, end)``, on its clock.

    The profiler's own host tracer is off: on a TPU v5e it made each
    ``device_put`` of a 19 MB batch take about 260 ms against 7 ms, and
    a traced window serve a ninth of the images.  So the loop keeps its
    spans here, and ``bench/trace.py`` ties them to the device's trace.
    """

    def __init__(self):
        self.spans: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        a = time.perf_counter()
        try:
            yield
        finally:
            self.spans.append((name, a, time.perf_counter()))


def _no_span(name: str):
    return contextlib.nullcontext()


class Cell:
    """One cell, built and warm: the model and its weights from the seed,
    an image pool per resolution, and ``AsyncServeFrontend`` with every
    bucket program of the mix compiled and run."""

    def __init__(self, cell: Dict, cfg: Dict, mix: Dict, seed: int, *,
                 precision: Optional[str] = None, log=print):
        import jax
        import numpy as np

        from bench import loadgen, work
        from repro.core.graph import GraphBuilder
        from repro.models.cnn import GraphModel
        from repro.serve import AsyncServeFrontend

        self.cell, self.cfg, self.mix = cell, cfg, mix
        ss_w, ss_img, ss_traffic, self.ss_check = _seeds(seed)
        self.mod = mod = load_module(
            BENCH / "configs" / f"{cell['config']}.py",
            f"bench_config_{cell['config']}")

        def builder(in_shape, policy):
            b = GraphBuilder(in_shape, policy)
            mod.build(b, cfg)
            return b.graph()

        self.size = int(cfg["image_size"])
        c = int(cfg["in_channels"])
        shapes = {s: (s, s, c)
                  for s, _ in loadgen.size_pairs(mix, self.size)}
        key = jax.random.wrap_key_data(
            np.asarray(ss_w.generate_state(2), np.uint32))
        self.params = jax.block_until_ready(
            jax.jit(lambda k: mod.init(k, cfg))(key))
        rng_img = np.random.default_rng(ss_img)
        self.pools = {s: rng_img.standard_normal(
            (int(mix["pool_images"]),) + shape, dtype=np.float32)
            for s, shape in shapes.items()}
        self.rng = np.random.default_rng(ss_traffic)
        model = GraphModel(builder, next(iter(shapes.values())),
                           name=cell["config"])
        if precision == "int8":
            # the control: the program's own int8 path, calibrated on
            # the first images of each pool
            from repro.quant import Calibrator, QuantPolicy
            n = min(int(mix["pool_images"]), max(mix["buckets"]))
            for s, shape in shapes.items():
                model.graph_plan((n,) + shape).warmup(
                    calibrate=Calibrator(self.pools[s][:n], self.params))
            precision = QuantPolicy()
        self.fe = AsyncServeFrontend(
            model, self.params,
            {shape: tuple(mix["buckets"]) for shape in shapes.values()},
            max_wait_ms=mix["max_wait_ms"], precision=precision)
        self.fe.warmup()
        plans, self.work = {}, {}
        for shape, progs in self.fe.programs.items():
            geom = "x".join(map(str, shape))
            for b in progs.buckets:
                gp = progs.graph_plan(b)
                plans[f"{geom}/b{b}"] = {
                    n: f"{p.algorithm} {p.config.key() if p.config else '-'}"
                    for n, p in gp.conv_plans.items()}
                self.work[geom, b] = work.graph_work(gp.base_graph
                                                     or gp.graph)
        log(json.dumps({"plans": plans}))
        self.serve(WARM_SECONDS)

    def serve(self, seconds: float, *, spans: Optional[HostSpans] = None,
              mix: Optional[Dict] = None):
        """Serve the mix for ``seconds`` and wait for every request sent;
        returns ``(sent, batches, requests, t0, t_end)``, the last two
        on the loop's clock.  ``spans`` records the loop's host spans."""
        from bench import loadgen
        from repro.serve import ServeRequest
        fe = self.fe
        n_batches, n_requests = len(fe.telemetry.batches), len(
            fe.telemetry.requests)
        clock = time.perf_counter
        t0 = clock()
        sent = loadgen.serve(
            fe, loadgen.make_source(mix or self.mix, t0, seconds,
                                    self.size, self.rng),
            self.pools, lambda rid, x: ServeRequest(rid=rid, images=x),
            clock, time.sleep, spans or _no_span)
        return (sent, fe.telemetry.batches[n_batches:],
                fe.telemetry.requests[n_requests:], t0, clock())

    def sample(self, served: List) -> List:
        """The requests to compare: a draw from the seed, and the
        request of the most images among ``served``."""
        import numpy as np
        if not served:
            return []
        rng = np.random.default_rng(self.ss_check)
        pick = set(rng.choice(len(served), min(
            len(served), int(self.mix["check_requests"])), replace=False))
        pick.add(max(range(len(served)), key=lambda i: served[i].images))
        return [served[i] for i in sorted(pick)]

    def free(self) -> None:
        """Drop the frontend and its programs before the reference runs."""
        self.fe = None
        gc.collect()


def run_cell(cell: Dict, cfg: Dict, mix: Dict, seed: int, seconds: float,
             trace: bool, peaks: Dict, device: Dict, *,
             precision: Optional[str] = None, log=print,
             bench: Optional[Dict] = None) -> Dict:
    """One run of one cell; returns the result dict (no device check:
    ``main`` makes it).  ``bench`` is ``BENCHMARK.json``'s content."""
    import jax

    from bench import trace as trace_mod
    from repro.core import convspec

    bench = bench or load_benchmark()
    with CompileCounter() as compiles:
        c = Cell(cell, cfg, mix, seed, precision=precision, log=log)
        resolutions0 = convspec.PLAN_STATS["resolutions"]
        compiles0, setup_misses = compiles.n, compiles.misses
        trace_dir = STATE / "trace"
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0      # see HostSpans
            jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        setup_s = time.perf_counter() - T_START
        spans = HostSpans() if trace else None
        sent, batches, requests, t0, t_end = c.serve(seconds, spans=spans)
        if trace:
            jax.profiler.stop_trace()
        resolutions = convspec.PLAN_STATS["resolutions"] - resolutions0
        in_window_compiles = compiles.n - compiles0
    stats = jax.devices()[0].memory_stats() or {}
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))

    served = [s for s in sent if s.status == "served"]
    failed = len(sent) - len(served)
    win = Window(seconds, t0, setup_s, sent, batches, requests, None,
                 c.work, peaks, t_end)
    result = {"correct": False, "attempted": len(sent), "failed": failed,
              # executables compiled anew in set-up: > 0 on a
              # checkout's first, cold run
              "setup_compiles": setup_misses}
    if trace:
        events = trace_mod.load(trace_mod.find_xplane(str(trace_dir)))
        events += trace_mod.host_events(
            events, [(trace_mod.WINDOW_SPAN, t0, t_end)] + spans.spans,
            batches[0].transfer_t1)
        win.trace = trace_mod.summarize(events)
        device.update(busy_s=win.trace.busy_s, window_s=win.trace.window_s)
        result["breakdown"] = {
            "device_ops": [list(x) for x in win.trace.device_ops],
            "idle_gaps": [list(x) for x in win.trace.idle_gaps]}
    metrics = read_metrics(bench["per_layer" if trace else "end_to_end"],
                           cell["name"], win)
    log(f"window: {len(sent)} requests sent, {len(served)} served, "
        f"{t_end - t0:.3f} s with the drain", file=sys.stderr)

    sample = c.sample(served)
    c.free()
    checks = {
        "logit_err": {"value": compare(c.mod, cfg, c.params, c.pools,
                                       sample),
                      "limit": float(cfg["logit_err_limit"])},
        "batches_off_dtype": {"value": sum(b.dtype != cfg["dtype"]
                                           for b in batches), "limit": 0},
        "unserved": {"value": failed, "limit": 0},
        "plan_resolutions_in_window": {"value": resolutions, "limit": 0},
        "compiles_in_window": {"value": in_window_compiles, "limit": 0},
    }
    result["correct"] = bool(sample) and all(
        v["value"] <= v["limit"] for v in checks.values())
    result["metrics"] = metrics
    result["device"] = device
    result["compared_images"] = sum(s.images for s in sample)
    result["checks"] = checks
    return result


def compare(mod, cfg: Dict, params, pools: Dict, sample: List) -> float:
    """Worst over the sampled images of max|out - ref| / max|ref|, the
    reference run in blocks of ``REF_BLOCK`` images of one resolution."""
    import jax
    import numpy as np
    if not sample:
        return float("inf")
    fn = jax.jit(lambda p, xb: mod.reference(p, xb, cfg))
    worst = 0.0
    for size, pool in pools.items():
        part = [s for s in sample if s.size == size]
        if not part:
            continue
        x = np.concatenate([pool[s.first:s.first + s.images] for s in part])
        got = np.concatenate([s.out for s in part]).astype(np.float64)
        pad = (-len(x)) % REF_BLOCK
        xp = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        ref = np.concatenate([np.asarray(fn(params, xp[i:i + REF_BLOCK]))
                              for i in range(0, len(xp), REF_BLOCK)])
        ref = ref[:len(x)].astype(np.float64)
        num = np.abs(got - ref).max(axis=1)
        den = np.maximum(np.abs(ref).max(axis=1), 1e-30)
        worst = max(worst, float((num / den).max()))
    return worst


def read_metrics(entries: List[Dict], cell_name: str, win: Window,
                 metrics_dir: Path = BENCH / "metrics") -> Dict:
    """Every metric of ``entries`` that the cell reports and whose reader
    finds something; the reader of ``<name>`` is ``read(win)`` in
    ``<name>.py``."""
    out = {}
    for m in entries:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        reader = load_module(metrics_dir / f"{m['name']}.py",
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(win)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_env()
    bench = load_benchmark()
    cell, config = find_cell(bench, args.workload)
    cfg = json.loads((ROOT / config["file"]).read_text())
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                     .read_text())
    try:
        device = check_device(int(cell["chips"]))
    except NoChip as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 2
    try:
        peaks = load_peaks(device["kind"])
    except KeyError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(f"bench: compile cache {enable_compile_cache()}", file=sys.stderr)
    result = run_cell(cell, cfg, mix, args.seed, args.seconds,
                      bool(args.trace), peaks, device, bench=bench)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
