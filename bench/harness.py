"""One run of one cell: set-up, the measured window or the traced stretch,
the check against the plain reference, and the result line.

A driver (``bench/drivers/<name>.py``) owns what differs between entry
points; it defines ``Driver(run)``, whose constructor makes the input and
warms up the program, and the methods

- ``window(seconds) -> dict``: the cell's end-to-end metrics but
  ``setup_s``;
- ``traced() -> dict``: a short stretch of the same traffic, run under the
  profiler, and what the per-layer readers need to know of it;
- ``hlo_texts() -> list[str]``: the executed programs' HLO, which the
  trace reduction classifies device ops against;
- ``answers() -> (list, list)``: every answer the timed path produced,
  ``(label, keys, values, counts)`` on the host, and the reference's
  expected counts for each; called once the device state is freed.
"""

from __future__ import annotations

import contextlib
import json
import shutil
import sys
import time

import numpy as np

from bench import registry, trace_reduce

CACHE_DIR = registry.ROOT / ".jax_cache"
TRACE_DIR = registry.ROOT / ".bench_trace"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def prng_key(seed: int):
    """A key from all 64 bits of ``seed`` (``jax.random.key`` keeps 32)."""
    import jax

    s = int(seed) % (1 << 64)
    return jax.random.wrap_key_data(
        np.array([s >> 32, s & 0xFFFFFFFF], np.uint32))


def enable_compile_cache() -> str:
    """JAX's persistent cache, at a fixed path inside the checkout (also
    where ``JAX_COMPILATION_CACHE_DIR`` names another, which two checkouts
    would share); every program is kept, however quickly it compiled."""
    import jax

    path = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, rehearse: bool):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" and not rehearse:
        raise NoChip(f"no TPU: JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


class Spans:
    """Host spans the benchmark puts around its calls into the program:
    ``(name, start, end)`` on ``time.perf_counter``, and in a traced
    stretch also a ``TraceAnnotation`` in the profiler's own trace."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax

        ann = (jax.profiler.TraceAnnotation(name) if self.annotate
               else contextlib.nullcontext())
        t = time.perf_counter()
        with ann:
            yield
        self.records.append((name, t, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        return [e - s for n, s, e in self.records if n == name]


class CompileWatch:
    """Counts JAX traces and backend compiles while it is entered, and the
    engine's plan-cache counters: the window must show none of either."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")
    _active: list = []
    _registered = False

    def __init__(self):
        self.counts = dict.fromkeys(self.EVENTS, 0)

    @classmethod
    def _listen(cls, event, duration, **_):
        for watch in cls._active:
            if event in watch.counts:
                watch.counts[event] += 1

    def __enter__(self):
        import jax

        from repro.core import plan_cache

        if not CompileWatch._registered:
            jax.monitoring.register_event_duration_secs_listener(
                CompileWatch._listen)
            CompileWatch._registered = True
        self._before = plan_cache.stats_snapshot()
        CompileWatch._active.append(self)
        return self

    def __exit__(self, *exc):
        from repro.core import plan_cache

        CompileWatch._active.remove(self)
        after = plan_cache.stats_snapshot()
        self.plan_cache = {k: after[k] - self._before[k] for k in after}

    def line(self) -> str:
        return (f"in the window: {self.counts[self.EVENTS[0]]} backend "
                f"compiles, {self.counts[self.EVENTS[1]]} traces; plan-cache "
                f"counter deltas {self.plan_cache}")

    @property
    def compiled(self) -> bool:
        pc = self.plan_cache
        return bool(self.counts[self.EVENTS[0]] or pc["compiles"]
                    or pc["derives"] or pc["autotunes"])


class Run:
    """What a driver is handed: the resolved cell and its settings."""

    def __init__(self, cell: registry.Cell, *, seed: int, devices,
                 rehearse: bool):
        self.cell = cell
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.devices = devices
        self.rehearse = rehearse
        self.app_mod = registry.load_module(cell.app_path)
        self.ref_mod = registry.load_module(cell.reference_path)
        self.spans = Spans()

    def make_items(self, sharding=None):
        """The cell's input, made on the device from the seed in one
        jitted call, laid out by ``sharding`` (default: the first chip)."""
        import jax
        from jax.sharding import SingleDeviceSharding

        sharding = sharding or SingleDeviceSharding(self.devices[0])
        gen = jax.jit(lambda k: self.app_mod.generate(self.cfg, k),
                      out_shardings=sharding)
        items = gen(prng_key(self.seed))
        return items.block_until_ready()

    def expected(self, items_host) -> np.ndarray:
        return self.ref_mod.counts(items_host, self.cfg)


def run_jobs(job, seconds: float):
    """Jobs back to back; the window closes when the first job that ends
    after ``seconds`` has ended.  Returns ``(window_s, outputs)``."""
    outs = []
    t0 = time.perf_counter()
    while True:
        outs.append(job())
        window = time.perf_counter() - t0
        if window >= seconds:
            return window, outs


def fetch(res):
    """A result on the host: the job is not done before the user has it."""
    return tuple(np.asarray(a) for a in (res.keys, res.values, res.counts))


def wrong_keys(answer, expected: np.ndarray) -> int:
    """Keys whose key id, value or count differs from the reference, plus
    any count past the key space."""
    keys, values, counts = answer
    K = expected.shape[0]
    values = np.asarray(values).reshape(values.shape[0], -1)[:, 0]
    bad = ((np.asarray(keys)[:K] != np.arange(K))
           | (values[:K].astype(np.int64) != expected)
           | (np.asarray(counts)[:K].astype(np.int64) != expected))
    return int(bad.sum()) + int(np.count_nonzero(np.asarray(counts)[K:]))


def check(answers, expected) -> dict:
    """``wrong_keys`` summed over every answer; exact, so its limit is 0."""
    wrong = [wrong_keys(a[1:], e) for a, e in zip(answers, expected)]
    return {"answers": len(answers),
            "failed": sum(1 for w in wrong if w),
            "wrong_keys": sum(wrong)}


def peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks_for(kind: str, rehearse: bool):
    with open(registry.BENCH_DIR / "peaks.json") as f:
        table = json.load(f)["peaks"]
    if kind in table:
        return table[kind]
    if rehearse:
        return None
    raise KeyError(f"no published peaks for device kind {kind!r} in "
                   f"bench/peaks.json")


class TraceView:
    """What a per-layer reader sees of the traced stretch."""

    def __init__(self, summary, info, run: Run, peaks):
        self.summary = summary
        self.info = info
        self.run = run
        self.peaks = peaks


def read_per_layer(cell, view: TraceView) -> dict:
    out = {}
    for m in cell.per_layer:
        value = registry.load_module(cell.metric_path(m["name"])).read(view)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(cell: registry.Cell, *, seed: int, seconds: float, trace: bool,
            rehearse: bool, t0: float) -> dict:
    """One run; returns the result record (printed by ``bench/run.py``)."""
    import jax

    devices = devices_for(cell.chips, rehearse)
    log(f"device: {device_info(devices)}; compile cache "
        f"{enable_compile_cache()}")
    run = Run(cell, seed=seed, devices=devices, rehearse=rehearse)
    driver = registry.load_module(cell.driver_path).Driver(run)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f}s")

    result: dict = {}
    if not trace:
        with CompileWatch() as watch:
            e2e = driver.window(seconds)
        print(watch.line(), flush=True)
        if watch.compiled:
            log("warning: something compiled inside the measured window")
        e2e["setup_s"] = setup_s
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]} for m in cell.end_to_end}
    else:
        texts = driver.hlo_texts()
        out_dir = TRACE_DIR / cell.name
        shutil.rmtree(out_dir, ignore_errors=True)
        out_dir.mkdir(parents=True)
        run.spans.annotate = True
        jax.profiler.start_trace(str(out_dir))
        try:
            info = driver.traced()
        finally:
            jax.profiler.stop_trace()
            run.spans.annotate = False
        t = time.perf_counter()
        summary = trace_reduce.summarize(
            trace_reduce.find_xplane(out_dir), texts,
            n_devices=len(devices))
        shutil.rmtree(out_dir, ignore_errors=True)
        log(f"trace reduced in {time.perf_counter() - t:.1f}s: "
            f"{summary.describe()}")
        view = TraceView(summary, info, run,
                         peaks_for(devices[0].device_kind, rehearse))
        metrics = read_per_layer(cell, view)
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_gaps(10)}
    device = device_info(devices)
    device["memory_peak_bytes"] = peak_bytes(devices)
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    t = time.perf_counter()
    answers, expected = driver.answers()
    del driver
    checked = check(answers, expected)
    log(f"reference compared {checked['answers']} answers in "
        f"{time.perf_counter() - t:.1f}s")
    checks = {"wrong_keys": {"value": checked["wrong_keys"], "limit": 0}}
    correct = (checked["wrong_keys"] <= 0 and checked["answers"] >= 1)
    result = {"correct": correct, "attempted": checked["answers"],
              "failed": checked["failed"], "metrics": metrics,
              "device": device, **result, "checks": checks}
    return result


def check_lines(result: dict) -> list[str]:
    return [f"check {name}: {c['value']} (limit {c['limit']})"
            for name, c in result["checks"].items()]
