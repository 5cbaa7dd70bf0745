"""Reduce a profiler trace to what the per-layer metrics read.

Input: the ``.xplane.pb`` that ``jax.profiler`` wrote for a traced stretch,
and the HLO text of the programs that ran in it.  Output
(:class:`Summary`):

- the traced window: from the first to the last host span the benchmark
  opened (``bench.*``), on the trace's own clock;
- for each chip, the union of its device-op intervals inside the window
  (busy), and the gaps between them (idle), each gap labelled by the host
  span that overlaps it most;
- for each chip, device seconds per op class.  An op is classified by its
  HLO instruction in the program that was running: a collective, a sort
  (or a Pallas radix/segment kernel), a contraction (a dot or convolution,
  in the op or fused inside it; an op that XLA rewrote from a JAX
  ``dot_general`` or convolution, as the instruction's ``op_name``
  metadata records, such as a one-hot product lowered to a compare and a
  reduce; or a Pallas fold kernel), a container
  (``while``/``conditional``/``call``, whose body ops are traced on their
  own and are not counted twice), or anything else by opcode.

On a TPU the device ops are the events of the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane.  A CPU trace has no device plane: there the
events that carry an ``hlo_op`` stat stand in for device 0, so the
reduction can be tested without a chip.
"""

from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

COLLECTIVES = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "collective-broadcast", "ragged-all-to-all")
CONTAINERS = ("while", "conditional", "call")
SORT_KERNELS = ("_hist_kernel", "_scatter_kernel", "_segment_kernel")
FOLD_KERNELS = ("_block_fold_kernel",)
SPAN_PREFIX = "bench."
TOP_GAPS = 10  # longest idle gaps kept per chip
#: a device trace that ends this long before the last span has dropped ops
DROP_SLACK_NS = 100e6

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLS = re.compile(r"\b(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
#: the JAX op an instruction was lowered from, by its ``op_name`` metadata
_SOURCE = re.compile(r'op_name="[^"]*/(dot_general|conv_general_dilated)"')
_SOURCE_OPCODE = {"dot_general": "dot", "conv_general_dilated": "convolution"}


# ---------------------------------------------------------------------------
# HLO text -> op class
# ---------------------------------------------------------------------------


def _opcode(rest: str) -> str:
    """Opcode of an instruction's right-hand side (after ``name =``)."""
    i = 0
    if rest.startswith("("):  # tuple shape
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        i += 1
    else:
        while i < len(rest) and not rest[i].isspace():
            i += 1
    m = _OPCODE.match(rest, i)
    return m.group(1) if m else ""


def _source_ops(line: str) -> set:
    """The contraction an instruction was lowered from, as an opcode."""
    return {_SOURCE_OPCODE[m] for m in _SOURCE.findall(line)}


@dataclasses.dataclass
class _Instr:
    opcode: str
    calls: tuple
    line: str


@dataclasses.dataclass
class Programs:
    """What the trace reduction knows of the programs that ran:
    ``classes[module][instruction]`` and the opcodes inside each
    computation, ``inner[computation]``, fused and called ones included."""

    classes: dict
    inner: dict

    def merged(self) -> dict:
        out = {}
        for table in self.classes.values():
            out.update(table)
        return out


def parse_hlo(texts) -> Programs:
    """Index the HLO text of one or more modules."""
    modules: dict[str, dict[str, _Instr]] = {}
    comps: dict[str, list] = {}
    module = comp = None
    for line in "\n".join(texts).splitlines():
        if line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
            modules[module] = {}
            continue
        if module is None:
            continue
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()
            comp = (head[1] if head[0] == "ENTRY" else head[0]).lstrip("%")
            comps[comp] = []
            continue
        m = _INSTR.match(line)
        if m is None or comp is None:
            continue
        ins = _Instr(_opcode(m.group(2)), tuple(_CALLS.findall(line)), line)
        modules[module][m.group(1)] = ins
        comps[comp].append(ins)

    inner: dict[str, set] = {}

    def ops_in(c, depth=0):
        if c not in inner:
            inner[c] = set()  # guards against a cycle
            ops: set = set()
            if depth < 8:
                for ins in comps.get(c, ()):
                    ops.add(ins.opcode)
                    ops |= _source_ops(ins.line)
                    for cc in ins.calls:
                        ops |= ops_in(cc, depth + 1)
            inner[c] = ops
        return inner[c]

    classes = {}
    for mod, instrs in modules.items():
        classes[mod] = {}
        for name, ins in instrs.items():
            ops = {ins.opcode} | _source_ops(ins.line)
            if ins.opcode == "fusion":
                for c in ins.calls:
                    ops |= ops_in(c)
            classes[mod][name] = classify(ins.opcode, ops, ins.line)
    for c in comps:
        ops_in(c)
    return Programs(classes=classes, inner=inner)


def classify_event(event: str, table: dict, programs: Programs
                   ) -> tuple[str, str]:
    """``(instruction name, class)`` of a device event.  On a TPU the
    event's name is the instruction's HLO text (``%name = shape op(...)``),
    elsewhere its bare name."""
    m = _INSTR.match(event)
    if m is None:
        name = event.lstrip("%")
        opcode, calls = name.split(".")[0], ()
    else:
        name = m.group(1)
        opcode, calls = _opcode(m.group(2)), tuple(_CALLS.findall(event))
    cls = table.get(name)
    if cls is None:
        ops = {opcode}
        for c in calls:
            ops |= programs.inner.get(c, set())
        cls = classify(opcode, ops, event)
    return name, cls


def classify(opcode: str, ops: set, line: str = "") -> str:
    base = {o.removesuffix("-start").removesuffix("-done") for o in ops}
    if base & set(COLLECTIVES):
        return "collective"
    if opcode == "custom-call":
        if any(k in line for k in SORT_KERNELS):
            return "sort"
        if any(k in line for k in FOLD_KERNELS):
            return "contraction"
        return "custom-call"
    if "sort" in base:
        return "sort"
    if base & {"dot", "convolution"}:
        return "contraction"
    if opcode in CONTAINERS:
        return "control"
    return opcode or "unknown"


# ---------------------------------------------------------------------------
# xplane -> events
# ---------------------------------------------------------------------------


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


@dataclasses.dataclass
class Events:
    """Events of one trace, on its own clock (nanoseconds)."""

    ops: dict  # device -> (names list, starts ns array, ends ns array)
    modules: dict  # device -> (names, starts, ends) of program runs
    spans: list  # (name, start, end) of the benchmark's host spans


def load(path: Path) -> Events:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    planes = list(pd.planes)
    on_chip = any(_DEVICE_PLANE.match(p.name) for p in planes)
    ops, modules, spans, cpu_ops = {}, {}, [], ([], [], [])
    for plane in planes:
        m = _DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None and line.name in ("XLA Ops", "XLA Modules"):
                names, starts, durs = [], [], []
                for ev in line.events:
                    names.append(ev.name)
                    starts.append(ev.start_ns)
                    durs.append(ev.duration_ns)
                s = np.asarray(starts, np.float64)
                rec = (names, s, s + np.asarray(durs, np.float64))
                (ops if line.name == "XLA Ops" else modules)[
                    int(m.group(1))] = rec
            elif m is None and plane.name.startswith("/host"):
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif not on_chip and line.name != "python":
                        stats = dict(ev.stats)
                        if "hlo_op" in stats:
                            cpu_ops[0].append(str(stats["hlo_op"]))
                            cpu_ops[1].append(ev.start_ns)
                            cpu_ops[2].append(ev.start_ns + ev.duration_ns)
    if not on_chip and cpu_ops[0]:
        ops[0] = (cpu_ops[0], np.asarray(cpu_ops[1], np.float64),
                  np.asarray(cpu_ops[2], np.float64))
    return Events(ops=ops, modules=modules, spans=sorted(spans,
                                                         key=lambda s: s[1]))


# ---------------------------------------------------------------------------
# Summary
# ---------------------------------------------------------------------------


def union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray,
                                                         np.ndarray]:
    """Disjoint, sorted intervals covering the given ones."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    reach = np.maximum.accumulate(e)
    new = np.ones(s.size, bool)
    new[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(new)
    seg_end = np.append(reach[idx[1:] - 1], reach[-1])
    return s[idx], seg_end


@dataclasses.dataclass
class Summary:
    window_s: float
    busy: dict  # device -> busy seconds inside the window
    class_s: dict  # device -> {class: seconds}
    op_s: dict  # (class, op name) -> seconds summed over devices
    gaps: list  # (seconds, label, device): each chip's longest
    n_events: int
    n_devices: int
    untraced_s: float = 0.0

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips used."""
        return sum(self.busy.values()) / self.n_devices

    def idle_pct(self) -> float | None:
        if not self.busy or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def class_ms(self, cls: str) -> float | None:
        """Device ms in op class ``cls`` on the chip with the most, or
        None when the trace holds no device op at all."""
        if not self.class_s:
            return None
        return 1e3 * max(c.get(cls, 0.0) for c in self.class_s.values())

    def class_ms_per_job(self, cls: str, jobs: int) -> float | None:
        """``class_ms`` per job of a stretch of ``jobs`` whole jobs.  Where
        the profiler dropped the stretch's last events, the untraced tail
        is taken to run as the traced part did (a job is one loop of like
        steps), so the traced time is scaled by window / traced window."""
        ms = self.class_ms(cls)
        if ms is None:
            return None
        return ms / jobs * (self.window_s + self.untraced_s) / self.window_s

    def top_ops(self, n: int) -> list:
        ranked = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        return [[f"{cls}:{name}", s / self.n_devices]
                for (cls, name), s in ranked]

    def top_gaps(self, n: int) -> list:
        return [[label, s] for s, label, _ in sorted(self.gaps,
                                                     key=lambda g: -g[0])[:n]]

    def describe(self) -> str:
        return (f"{self.n_events} device events on {len(self.busy)} "
                f"chip(s), window {self.window_s:.6f}s, busy "
                f"{self.busy_s:.6f}s, untraced tail {self.untraced_s:.6f}s")


def _label(spans, a: float, b: float) -> str:
    best, label = 0.0, "no bench span"
    for name, s, e in spans:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, label = ov, name
    return label


def summarize(path: Path, hlo_texts=(), *, n_devices: int = 1) -> Summary:
    ev = load(path)
    programs = parse_hlo(hlo_texts)
    merged = programs.merged()

    if ev.spans:
        lo = min(s for _, s, _ in ev.spans)
        hi = max(e for _, _, e in ev.spans)
    else:
        ends = [x for _, s, e in ev.ops.values() if s.size
                for x in (s.min(), e.max())]
        lo, hi = (min(ends), max(ends)) if ends else (0.0, 0.0)
    # The profiler keeps a bounded number of device events and drops the
    # rest: a device trace that stops while a benchmark span is still open
    # ends the window at its last op, so the untraced tail counts neither
    # as busy nor as idle.  ``untraced_s`` says how much was cut.
    last = [e.max() for _, s, e in ev.ops.values() if s.size]
    untraced = 0.0
    if last and ev.spans and max(last) < hi - DROP_SLACK_NS:
        untraced, hi = (hi - max(last)) * 1e-9, max(last)

    busy, class_s, op_s, gaps, n_events = {}, {}, {}, [], 0
    for dev, (names, starts, ends) in ev.ops.items():
        s = np.clip(starts, lo, hi)
        e = np.clip(ends, lo, hi)
        keep = e > s
        n_events += int(keep.sum())
        us, ue = union(s[keep], e[keep])
        busy[dev] = float((ue - us).sum()) * 1e-9

        mods = _module_of(ev.modules.get(dev), starts)
        dur = (e - s) * 1e-9
        totals: dict[tuple, float] = {}
        for i in np.flatnonzero(keep):
            key = (mods[i] if mods is not None else None, names[i])
            totals[key] = totals.get(key, 0.0) + dur[i]
        per_class: dict[str, float] = {}
        for (mod, event), secs in totals.items():
            name, cls = classify_event(
                event, programs.classes.get(mod, merged), programs)
            if cls == "control":
                continue
            per_class[cls] = per_class.get(cls, 0.0) + secs
            op_s[(cls, name)] = op_s.get((cls, name), 0.0) + secs
        class_s[dev] = per_class

        gap_s = np.concatenate([[lo], ue])
        gap_e = np.concatenate([us, [hi]])
        longest = np.argsort(gap_s - gap_e)[:TOP_GAPS]
        for a, b in zip(gap_s[longest], gap_e[longest]):
            if b > a:
                gaps.append(((b - a) * 1e-9, _label(ev.spans, a, b), dev))
    return Summary(window_s=(hi - lo) * 1e-9, busy=busy, class_s=class_s,
                   op_s=op_s, gaps=gaps, n_events=n_events,
                   n_devices=max(n_devices, len(busy) or 1),
                   untraced_s=untraced)


def _module_of(modules, starts: np.ndarray):
    """Program name (``HloModule`` name) running at each op's start."""
    if modules is None or not modules[0]:
        return None
    names, ms, me = modules
    order = np.argsort(ms)
    ms, me = ms[order], me[order]
    clean = [re.sub(r"\(\d+\)$", "", names[i]) for i in order]
    j = np.searchsorted(ms, starts, side="right") - 1
    out = []
    for i, k in enumerate(j):
        out.append(clean[k] if k >= 0 and starts[i] <= me[k] else None)
    return out
