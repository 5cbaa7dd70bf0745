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
  ``(label, keys, values, counts)`` on the host (``values`` a pytree of
  columns), and the reference's expected table for each; called once the
  device state is freed.

The reference (``bench/reference/<app>.py``, NumPy only, importing
nothing of the program) defines one of

- ``table(items, cfg) -> {"values": {name: ndarray[K, ...]}, "counts":
  ndarray[K]}``: a flat dict of named value columns and the count of each
  of the ``K`` keys;
- ``counts(items, cfg) -> ndarray[K]``: a job whose every value is a sum
  of ones, the table whose one column ``value`` equals the counts.

``items`` is the host copy of the input: one array, or a pytree of
columns.  The program's values are named the same way (``columns``): a
single array is the column ``value``, a pytree's leaves take their path
(dict keys, tuple positions) joined by ``.``.  A column is compared
exactly unless the configuration's file states a tolerance for it:
``"tolerance": {name: {"rtol": r, "atol": a, "why": "<reason>"}}``, which
``tolerances`` checks when the run is set up.
"""

from __future__ import annotations

import collections
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
        self.tolerance = tolerances(self.cfg, self.app_mod, self.ref_mod)
        self.spans = Spans()

    def make_items(self, sharding=None):
        """The cell's input (one array or a pytree of columns), made on
        the device from the seed in one jitted call, every leaf laid out
        by ``sharding`` (default: the first chip)."""
        import jax
        from jax.sharding import SingleDeviceSharding

        sharding = sharding or SingleDeviceSharding(self.devices[0])
        gen = jax.jit(lambda k: self.app_mod.generate(self.cfg, k),
                      out_shardings=sharding)
        return jax.block_until_ready(gen(prng_key(self.seed)))

    def expected(self, items_host) -> dict:
        return reference_table(self.ref_mod, items_host, self.cfg)


def count_table(counts: np.ndarray) -> dict:
    """The table of a job whose every value is a sum of ones."""
    return {"values": {"value": counts}, "counts": counts}


def reference_table(ref_mod, items_host, cfg) -> dict:
    if hasattr(ref_mod, "table"):
        return ref_mod.table(items_host, cfg)
    return count_table(ref_mod.counts(items_host, cfg))


def _shape_pair(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)
            and all(isinstance(d, int) for d in x[0]))


def map_item_shapes(fn, app_mod, cfg):
    """``fn(shape, dtype)`` for each leaf of ``items_shape(cfg)``: one
    ``(shape, dtype)`` pair for a single array, or a dict or tuple of them
    for columns."""
    import jax

    return jax.tree_util.tree_map(lambda sd: fn(*sd),
                                  app_mod.items_shape(cfg),
                                  is_leaf=_shape_pair)


def tolerances(cfg, app_mod, ref_mod) -> dict:
    """The configuration's stated tolerances, ``{column: (rtol, atol)}``.

    Raises unless each names a float column that the reference returns and
    gives its reason: integers are always exact.  The reference's columns
    are read from its table of an input of no rows."""
    stated = cfg.get("tolerance", {})
    if not stated:
        return {}
    empty = map_item_shapes(lambda shape, dtype: np.zeros(
        (0,) + tuple(shape[1:]), dtype), app_mod, cfg)
    with np.errstate(all="ignore"):
        cols = columns(reference_table(ref_mod, empty, cfg)["values"])
    out = {}
    for name, t in stated.items():
        if not isinstance(t, dict) or not str(t.get("why", "")).strip():
            raise ValueError(f"tolerance on {name!r} gives no 'why'")
        if set(t) - {"rtol", "atol", "why"}:
            raise ValueError(f"tolerance on {name!r}: unknown keys "
                             f"{sorted(set(t) - {'rtol', 'atol', 'why'})}")
        if name not in cols:
            raise ValueError(f"tolerance on {name!r}, a column the "
                             f"reference does not return ({sorted(cols)})")
        if not np.issubdtype(cols[name].dtype, np.floating):
            raise ValueError(f"tolerance on {name!r}, a {cols[name].dtype} "
                             f"column: integers are compared exactly")
        rtol, atol = float(t.get("rtol", 0)), float(t.get("atol", 0))
        if not (rtol >= 0 and atol >= 0):
            raise ValueError(f"tolerance on {name!r}: rtol and atol must "
                             f"be numbers >= 0")
        out[name] = (rtol, atol)
    return out


def run_jobs(start, finish, seconds: float, ahead: int = 0):
    """Jobs back to back, ``ahead`` of them dispatched beyond the one the
    host waits for, so that the chip stays fed while the host stands still.

    ``start()`` dispatches a job and returns its handle; ``finish(handle)``
    waits for it and returns its answer on the host.  No job starts once
    ``seconds`` have passed; the window closes when every job started has
    finished, so all of that work counts over all of that time (with
    ``ahead = 0``: when the first job that ends after ``seconds`` has
    ended).  Returns ``(window_s, outputs)``."""
    outs, pending = [], collections.deque()
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        pending.append(start())
        while len(pending) > ahead:
            outs.append(finish(pending.popleft()))
    while pending:
        outs.append(finish(pending.popleft()))
    return time.perf_counter() - t0, outs


def fetch(res):
    """A result on the host: the job is not done before the user has it."""
    import jax

    return (np.asarray(res.keys),
            jax.tree_util.tree_map(np.asarray, res.values),
            np.asarray(res.counts))


def _name(entry) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def columns(values) -> dict:
    """An answer's value columns by name: a single array is ``value``, a
    pytree's leaves are named by their path, joined by ``.``."""
    import jax

    leaves = jax.tree_util.tree_flatten_with_path(values)[0]
    return {".".join(_name(e) for e in path) or "value": np.asarray(leaf)
            for path, leaf in leaves}


def _differs(got, want: np.ndarray, tol=None) -> np.ndarray:
    """Per key of ``want``'s ``K``: whether any element of its row differs
    (beyond ``tol = (rtol, atol)``, as ``np.isclose`` measures it against
    the reference; NaN matches NaN).  A row the program lacks differs; so
    does every row where the column's shape per key is not the
    reference's."""
    got = np.asarray(got)
    K = want.shape[0]
    bad = np.ones(K, bool)
    if got.shape[1:] != want.shape[1:]:
        return bad
    n = min(K, got.shape[0])
    g, w = got[:n].reshape(n, -1), want[:n].reshape(n, -1)
    if (tol is None and np.issubdtype(g.dtype, np.integer)
            and np.issubdtype(w.dtype, np.integer)):
        d = g.astype(np.int64) != w.astype(np.int64)
    else:
        rtol, atol = tol or (0.0, 0.0)
        d = ~np.isclose(g.astype(np.float64), w.astype(np.float64),
                        rtol=rtol, atol=atol, equal_nan=True)
    bad[:n] = d.any(axis=1)
    return bad


def wrong_keys(answer, expected: dict, tolerance=None) -> int:
    """Keys whose key id or count differs from the reference table, or
    any element of any value column beyond that column's tolerance (exact
    where none is stated), plus any count past the key space.  A column
    missing from the answer, or one the reference lacks, makes every key
    wrong."""
    keys, values, counts = answer
    tolerance = tolerance or {}
    want = columns(expected["values"])
    got = columns(values)
    K = expected["counts"].shape[0]
    bad = (_differs(keys, np.arange(K))
           | _differs(counts, expected["counts"]))
    if set(got) != set(want):
        bad[:] = True
    else:
        for name, col in want.items():
            bad |= _differs(got[name], col, tolerance.get(name))
    return int(bad.sum()) + int(np.count_nonzero(np.asarray(counts)[K:]))


def largest_relative_errors(answers, expected) -> dict:
    """Per float column of the reference, the largest ``|got - want| /
    |want|`` over every answer (where ``want`` is finite and not 0)."""
    worst: dict = {}
    for a, e in zip(answers, expected):
        got = columns(a[2])
        for name, w in columns(e["values"]).items():
            if not np.issubdtype(w.dtype, np.floating):
                continue
            g = got.get(name)
            err = 0.0
            if g is not None and g.shape[1:] == w.shape[1:]:
                n = min(len(g), len(w))
                g64, w64 = g[:n].astype(np.float64), w[:n].astype(np.float64)
                ok = np.isfinite(w64) & (w64 != 0)
                with np.errstate(all="ignore"):
                    rel = np.abs(g64[ok] - w64[ok]) / np.abs(w64[ok])
                err = float(np.nanmax(rel, initial=0.0))
            worst[name] = max(worst.get(name, 0.0), err)
    return worst


def check(answers, expected, tolerance=None) -> dict:
    """``wrong_keys`` summed over every answer; exact, so its limit is 0.
    Logs the largest relative error of each float column."""
    wrong = [wrong_keys(a[1:], e, tolerance) for a, e in zip(answers, expected)]
    for name, err in largest_relative_errors(answers, expected).items():
        rtol, atol = (tolerance or {}).get(name, (0.0, 0.0))
        log(f"column {name}: largest relative error {err!r} "
            f"(rtol {rtol!r}, atol {atol!r})")
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
    checked = check(answers, expected, run.tolerance)
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
