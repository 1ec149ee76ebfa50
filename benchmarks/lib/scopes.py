"""The step program's device time by model-layer scope, off the trace.

    python -m benchmarks.lib.scopes trace.xplane.pb

``tracered.py`` reads a trace through ``jax.profiler.ProfileData``, which
gives each ``XLA Ops`` event its HLO text, start and duration — and not
the op's *event metadata*, where a TPU trace keeps the rest (looked at by
hand on ``TPU v5 lite``, jax 0.9.0; both recorded traces under
``testdata/`` are such): ``tf_op``, the op's path on JAX's name stack
(``jit(_run)/while/body/closed_call/jvp(ddl.attn)/dot_general:``), XLA's
own ``flops`` and ``bytes_accessed`` for one execution, ``hlo_category``;
the plane's stats hold the chip's ``peak_teraflops_per_second`` and
``peak_hbm_bw_gigabytes_per_second``.  So this module reads the file's
protobuf wire format itself — the few fields of ``XSpace`` it needs, by
their numbers in ``xplane.proto`` — and imports no protobuf library and
no tensorflow.

What the program writes there: every op of a train step is traced under
a ``jax.named_scope`` of ``ddl_tpu/ops/naming.py:SCOPE_NAMES`` (and the
Pallas kernels under their own ``ddl_flash_*`` frame inside it).  A scope
survives every transform as a frame of the path:

- forward      ``jit(_run)/.../jvp(ddl.attn)/dot_general``
- backward     ``.../transpose(jvp(...))/checkpoint/ddl.attn/dot_general``
- recomputed   ``.../checkpoint/rematted_computation/ddl.attn/dot_general``

The innermost ``ddl.`` frame is the op's scope; ``rematted_computation``
anywhere in the path makes it a recomputation, else ``transpose(`` a part
of the backward pass, else of the forward pass (the optimizer's update
reads forward).  An op family that is a kernel (``ddl_flash_*``;
``ragged-dot-*`` / ``ddl_gmm*``) is counted as the kernel whatever scope
it stands in, or none — XLA's ``ragged-dot-none`` calls keep no path (my
chip runs, PR 34): the older ``flash_device_share`` and
``gmm_device_share`` read those families.  Everything else under no
``ddl.`` frame is *unscoped* — XLA's own copies carry no ``tf_op`` at all.

A fusion takes ONE path, its matmul's where it has one.  A producer or a
consumer that XLA fused into another scope's op is therefore counted with
that op.  For norms and casts beside matmuls that is a lead of below
~1 % of a scope's time, not a measurement; for adamw it is most of the
optimizer: XLA fuses a dense weight's whole update into the
weight-gradient matmul (``multiply_add_fusion = (param, mu, nu)
fusion(...)`` under ``.../checkpoint/ddl.mlp/dot_general``, Mistral, my
chip run, PR 34), so ``ddl.optimizer`` keeps only what has no such
matmul to ride: norm gains, the embedding, expert stacks behind XLA's
grouped-matmul kernels.

Times are own times (``tracered.self_times``: a ``%while`` less its
body), of the ops inside the step program's executions, clipped to
``tracered.window_of`` and averaged over the chips — the window and the
arithmetic of ``tracered.reduce``'s ``device_ops``, without its top-ten
cut.  By construction

    sum of the groups of :data:`GROUPS` + other scopes + kernels + unscoped
        = the step programs' own time,

and the tests hold the readers under ``benchmarks/layers/`` to it.
"""

from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
import struct
import sys
import tempfile
import time
from typing import Dict, Iterator, List, Optional, Tuple

from benchmarks.lib import tracered

# -- the wire format ----------------------------------------------------------
#
# Field numbers (tsl/profiler/protobuf/xplane.proto):
#   XSpace          1 planes
#   XPlane          2 name, 3 lines, 4 event_metadata (map: 1 key, 2 value),
#                   5 stat_metadata (map), 6 stats
#   XLine           2 name, 3 timestamp_ns, 4 events
#   XEvent          1 metadata_id, 2 offset_ps, 3 duration_ps
#   XEventMetadata  1 id, 2 name, 5 stats
#   XStatMetadata   1 id, 2 name
#   XStat           1 metadata_id, 2 double, 3 uint64, 4 int64, 5 str,
#                   6 bytes, 7 ref (the id of a stat_metadata whose NAME is
#                   the value)

Span = Tuple[int, int]  # a length-delimited field's payload, [start, end)


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = buf[i]
    i += 1
    if x < 0x80:
        return x, i
    x &= 0x7F
    shift = 7
    while True:
        y = buf[i]
        i += 1
        x |= (y & 0x7F) << shift
        if y < 0x80:
            return x, i
        shift += 7


def fields(buf: bytes, i: int, end: int) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one message's top level: an
    int for a varint, a float for a fixed64 (only doubles are kept so
    here), a :data:`Span` for a length-delimited field."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire == 1:
            value = struct.unpack_from("<d", buf, i)[0]
            i += 8
        elif wire == 5:
            value = None
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield key >> 3, wire, value
    if i != end:
        raise ValueError("a field runs past its message: not an XSpace")


def put(field: int, value) -> bytes:
    """One field on the wire, the reader's counterpart — for the tests'
    made-up traces and ``tools/record_scoped_trace.py``'s cut: an int as
    a varint, a float as a double, a str or bytes length-delimited."""

    def varint(n: int) -> bytes:
        out = bytearray()
        while n >= 0x80:
            out.append(n & 0x7F | 0x80)
            n >>= 7
        return bytes(out + bytes([n]))

    if isinstance(value, int):
        return varint(field << 3) + varint(value)
    if isinstance(value, float):
        return varint(field << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return varint(field << 3 | 2) + varint(len(value)) + value


def _text(buf: bytes, span: Span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _stats(buf: bytes, spans: List[Span], stat_names: Dict[int, str]) -> dict:
    """``XStat`` messages as {stat's name: value}."""
    out = {}
    for a, b in spans:
        name, value = None, None
        for f, wire, v in fields(buf, a, b):
            if f == 1:
                name = stat_names.get(v)
            elif f in (2, 3):
                value = v
            elif f == 4:  # int64: two's complement in the varint
                value = v - (1 << 64) if v >= 1 << 63 else v
            elif f == 5:
                value = _text(buf, v)
            elif f == 7:
                value = stat_names.get(v, "")
        if name is not None and value is not None:
            out[name] = value
    return out


@dataclasses.dataclass
class OpMeta:
    """One HLO op as the plane's ``event_metadata`` describes it."""

    name: str  # the HLO text, as ProfileData's event name has it
    tf_op: str = ""
    flops: int = 0  # XLA's count for one execution
    bytes_accessed: int = 0
    hlo_category: str = ""


@dataclasses.dataclass
class DevicePlane:
    chip: int
    stats: dict  # the plane's own: peaks, device_type_string
    meta: Dict[int, OpMeta]
    ops: List[Tuple[float, float, int]]  # start s, end s, metadata id
    modules: List[Tuple[float, float, str]]  # start s, end s, program name


def _events(buf: bytes, line: Span):
    """(timestamp_ns, [(metadata_id, offset_ps, duration_ps)]) of a line."""
    timestamp_ns, events = 0, []
    for f, _, v in fields(buf, *line):
        if f == 3:
            timestamp_ns = v
        elif f == 4:
            mid = off = dur = 0
            for g, _, w in fields(buf, *v):
                if g == 1:
                    mid = w
                elif g == 2:
                    off = w
                elif g == 3:
                    dur = w
            events.append((mid, off, dur))
    return timestamp_ns, events


def _seconds(timestamp_ns: int, off_ps: int, dur_ps: int) -> Tuple[float, float]:
    # ProfileData's arithmetic (whole nanoseconds, cut not rounded), then
    # tracered.load's: the same doubles, so that a window taken from here
    # is the reduction's to the last bit.
    start_ns = float(timestamp_ns + off_ps // 1000)
    return start_ns * 1e-9, (start_ns + float(dur_ps // 1000)) * 1e-9


def read_planes(path: str) -> List[DevicePlane]:
    """The ``/device:TPU:<n>`` planes of an ``.xplane.pb``."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for f, wire, plane in fields(buf, 0, len(buf)):
        if f != 1 or wire != 2:
            continue
        parts: Dict[int, List[Span]] = {2: [], 3: [], 4: [], 5: [], 6: []}
        for g, w, v in fields(buf, *plane):
            if w == 2 and g in parts:
                parts[g].append(v)
        m = tracered.DEVICE_PLANE.match(_text(buf, parts[2][0]) if parts[2] else "")
        if not m:
            continue
        stat_names: Dict[int, str] = {}
        for entry in parts[5]:
            for g, _, v in fields(buf, *entry):
                if g == 2:  # the map entry's value, an XStatMetadata
                    sid, sname = 0, ""
                    for h, _, x in fields(buf, *v):
                        if h == 1:
                            sid = x
                        elif h == 2:
                            sname = _text(buf, x)
                    stat_names[sid] = sname
        meta: Dict[int, OpMeta] = {}
        for entry in parts[4]:
            for g, _, v in fields(buf, *entry):
                if g != 2:
                    continue
                mid, name, stat_spans = 0, "", []
                for h, _, x in fields(buf, *v):
                    if h == 1:
                        mid = x
                    elif h == 2:
                        name = _text(buf, x)
                    elif h == 5:
                        stat_spans.append(x)
                s = _stats(buf, stat_spans, stat_names)
                meta[mid] = OpMeta(
                    name=name, tf_op=str(s.get("tf_op", "")),
                    flops=int(s.get("flops", 0) or 0),
                    bytes_accessed=int(s.get("bytes_accessed", 0) or 0),
                    hlo_category=str(s.get("hlo_category", "")),
                )
        ops: List[Tuple[float, float, int]] = []
        modules: List[Tuple[float, float, str]] = []
        for line in parts[3]:
            name = next(
                (_text(buf, v) for g, _, v in fields(buf, *line) if g == 2), ""
            )
            if name not in (tracered.OPS_LINE, tracered.MODULES_LINE):
                continue
            ts, events = _events(buf, line)
            for mid, off, dur in events:
                a, b = _seconds(ts, off, dur)
                if name == tracered.OPS_LINE:
                    ops.append((a, b, mid))
                else:
                    modules.append((a, b, meta[mid].name if mid in meta else ""))
        ops.sort()
        modules.sort()
        planes.append(DevicePlane(
            chip=int(m.group(1)), stats=_stats(buf, parts[6], stat_names),
            meta=meta, ops=ops, modules=modules,
        ))
    return planes


# -- the classifier -----------------------------------------------------------

FRAME = re.compile(r"ddl[._][A-Za-z0-9_]+")
PASSES = ("forward", "backward", "recompute")
FLASH_FAMILIES = ("ddl_flash_",)
GMM_FAMILIES = ("ragged-dot-", "ddl_gmm")
KERNEL_FAMILIES = FLASH_FAMILIES + GMM_FAMILIES

#: Which scopes of ``ddl_tpu/ops/naming.py:SCOPE_NAMES`` a reader sums: the
#: innermost ``ddl.`` frame decides, so the shared expert inside
#: ``ddl.moe`` is an MLP's and the MLA projections inside ``ddl.attn`` are
#: attention's.  A scope of the table that is in no group (a later PR's)
#: is reported as ``other``, so that the sum stays whole.
GROUPS = {
    "attn": ("ddl.attn", "ddl.attn_gate", "ddl.mla_q", "ddl.mla_kv_up"),
    "mlp": ("ddl.mlp", "ddl.moe_shared"),
    "moe": ("ddl.moe", "ddl.moe_route", "ddl.moe_experts", "ddl.moe_combine"),
    "head": ("ddl.head", "ddl.embed", "ddl.patchify"),
    "optimizer": ("ddl.optimizer",),
}
_GROUP_OF = {scope: group for group, scopes in GROUPS.items() for scope in scopes}


def classify(tf_op: str) -> Tuple[Optional[str], Optional[str], str]:
    """(scope, frame, pass) of an op's path: the innermost ``ddl.`` frame
    (``None``: unscoped), the innermost frame of either kind (a kernel's
    ``ddl_flash_*`` among them), and the pass.  XLA joins the paths of ops
    it merged with ``;``: the first one that names a frame speaks."""
    paths = tf_op.split(";")
    path = next((p for p in paths if FRAME.search(p)), paths[0])
    frames = FRAME.findall(path)
    scope = next((f for f in reversed(frames) if f.startswith("ddl.")), None)
    if "rematted_computation" in path:
        which = "recompute"
    elif "transpose(" in path:
        which = "backward"
    else:
        which = "forward"
    return scope, (frames[-1] if frames else None), which


def is_kernel(family: str) -> bool:
    return family.startswith(KERNEL_FAMILIES)


# -- the table ----------------------------------------------------------------

Key = Tuple[Optional[str], Optional[str], str, str]  # scope, frame, pass, family


@dataclasses.dataclass
class Table:
    window_s: float
    step_own_s: float  # own seconds of the step programs' ops, mean over chips
    own: Dict[Key, float]  # seconds, mean over chips
    flops: Dict[Key, float]  # XLA's count over the window, mean over chips
    bytes: Dict[Key, float]
    peak_flops: Optional[float]  # the plane's own statement, per second
    peak_bytes: Optional[float]
    n_scoped_ops: int  # events under some ``ddl.`` frame

    def seconds(self, select) -> float:
        return sum(s for key, s in self.own.items() if select(*key))

    def group_s(self, group: str) -> float:
        """Own seconds under the scopes of ``GROUPS[group]`` (``other``:
        under a scope of no group), the kernels' families left out."""
        return self.seconds(
            lambda scope, frame, which, family:
            scope is not None and not is_kernel(family)
            and _GROUP_OF.get(scope, "other") == group
        )

    def kernels_s(self) -> float:
        return self.seconds(lambda s, f, w, family: is_kernel(family))

    def recompute_s(self) -> float:
        return self.seconds(lambda s, f, which, fam: which == "recompute")

    def unscoped_s(self) -> float:
        return self.seconds(
            lambda scope, f, w, family: scope is None and not is_kernel(family)
        )

    def summary(self) -> Dict[str, float]:
        """% of the window: the groups, ``other``, the kernels and
        ``unscoped`` (these add up to ``step_own``), and ``recompute``."""
        pct = 100.0 / self.window_s
        out = {g: pct * self.group_s(g) for g in list(GROUPS) + ["other"]}
        out.update(
            kernels=pct * self.kernels_s(), unscoped=pct * self.unscoped_s(),
            step_own=pct * self.step_own_s, recompute=pct * self.recompute_s(),
        )
        return out


def tabulate(planes: List[DevicePlane], step_program: str = "jit__run",
             window: Optional[tracered.Interval] = None) -> Optional[Table]:
    """``None`` where no chip ran the step program twice (no window)."""
    trace = tracered.Trace(
        ops={p.chip: p.ops for p in planes},
        modules={p.chip: p.modules for p in planes}, spans=[],
    )
    runs = tracered.step_programs(trace, step_program)
    if window is None:
        if sum(len(r) for r in runs.values()) < 2:
            return None
        window = tracered.window_of(trace, step_program)
    lo, hi = window
    if not planes or hi <= lo:
        return None
    own: Dict[Key, float] = {}
    flops: Dict[Key, float] = {}
    nbytes: Dict[Key, float] = {}
    n_scoped = 0
    share = 1.0 / len(planes)
    for plane in planes:
        keys: Dict[int, Key] = {}
        inside = []
        cursor, execs = 0, runs[plane.chip]
        for a, b, mid in plane.ops:  # sorted by start, as execs are
            while cursor < len(execs) and execs[cursor][1] <= a:
                cursor += 1
            if cursor == len(execs):
                break
            if a < execs[cursor][0] or b <= lo or a >= hi:
                continue
            inside.append((max(a, lo), min(b, hi), (mid, b - a)))
        for (mid, whole), secs in tracered.self_times(inside):
            key = keys.get(mid)
            if key is None:
                m = plane.meta.get(mid) or OpMeta(name="")
                family = tracered.op_family(tracered.op_name(m.name))
                key = keys[mid] = classify(m.tf_op) + (family,)
            own[key] = own.get(key, 0.0) + secs * share
        for a, b, (mid, whole) in inside:
            m = plane.meta.get(mid)
            if m is None:
                continue
            key = keys[mid]
            n_scoped += key[0] is not None
            if m.hlo_category in ("while", "conditional", "call") or whole <= 0:
                continue  # their bodies' ops carry the counts
            part = share * (b - a) / whole
            flops[key] = flops.get(key, 0.0) + m.flops * part
            nbytes[key] = nbytes.get(key, 0.0) + m.bytes_accessed * part
    stats = planes[0].stats
    tf = stats.get("peak_teraflops_per_second")
    gb = stats.get("peak_hbm_bw_gigabytes_per_second")
    return Table(
        window_s=hi - lo, step_own_s=sum(own.values()), own=own, flops=flops,
        bytes=nbytes, peak_flops=tf * 1e12 if tf else None,
        peak_bytes=gb * 1e9 if gb else None, n_scoped_ops=n_scoped,
    )


def rows(table: Table, top: int = 5) -> List[dict]:
    """The table frame x pass, largest first: seconds, % of the window,
    TFLOP/s and GB/s by XLA's own counts over the own time, and the
    ``top`` largest op families inside."""
    acc: Dict[Tuple[str, str], dict] = {}
    for key, secs in table.own.items():
        scope, frame, which, family = key
        if is_kernel(family):  # XLA's own kernels keep no path at all
            frame = frame if frame and frame.startswith("ddl_") else family
        elif scope is None:
            frame = "unscoped"
        row = acc.setdefault((frame, which), {
            "s": 0.0, "flops": 0.0, "bytes": 0.0, "families": {}})
        row["s"] += secs
        row["flops"] += table.flops.get(key, 0.0)
        row["bytes"] += table.bytes.get(key, 0.0)
        row["families"][family] = row["families"].get(family, 0.0) + secs
    out = []
    for (frame, which), row in sorted(acc.items(), key=lambda kv: -kv[1]["s"]):
        s = row["s"]
        out.append({
            "scope": frame, "pass": which, "s": s,
            "pct": 100.0 * s / table.window_s,
            "tflops": row["flops"] / s / 1e12 if s > 0 else 0.0,
            "gbs": row["bytes"] / s / 1e9 if s > 0 else 0.0,
            "families": [
                [n, v] for n, v in
                sorted(row["families"].items(), key=lambda kv: -kv[1])[:top]
            ],
        })
    return out


def render(table: Table) -> str:
    w = table.window_s
    lines = [
        f"window {w:.6f} s; peaks {(table.peak_flops or 0) / 1e12:.1f} TFLOP/s, "
        f"{(table.peak_bytes or 0) / 1e9:.1f} GB/s",
        "% of the window: " + ", ".join(
            f"{name} {value:.2f}" for name, value in table.summary().items()),
        f"{'scope':<22}{'pass':<10}{'s':>10}{'%':>8}{'TFLOP/s':>9}{'GB/s':>8}"
        "  largest op families (s)",
    ]
    for r in rows(table):
        fams = ", ".join(f"{n} {v:.4f}" for n, v in r["families"])
        lines.append(
            f"{r['scope']:<22}{r['pass']:<10}{r['s']:>10.5f}{r['pct']:>8.2f}"
            f"{r['tflops']:>9.1f}{r['gbs']:>8.1f}  {fams}"
        )
    return "\n".join(lines)


# -- this run's trace, for the readers ------------------------------------------

_MEMO: Dict[float, Optional[Table]] = {}


def _program_scopes() -> Tuple[str, ...]:
    """The program's scope table; empty on a program that has none."""
    try:
        from ddl_tpu.ops.naming import SCOPE_NAMES
    except ImportError:
        return ()
    return tuple(SCOPE_NAMES)


def _say(**fields_) -> None:
    print(json.dumps({"line": "scopes", **fields_}), flush=True)


def table_of_run(m: dict) -> Optional[Table]:
    """The table of the trace that the runner reduced into ``m["trace"]``.

    The readers are handed the reduced dict and not the file, which lives
    in ``<tmp>/ddl_bench_*/trace/`` until the runner's ``finally``: the
    newest ``*.xplane.pb`` there is taken, and kept only if its window is
    the reduction's to the last bit — the same file through the same
    arithmetic, a proof of identity.  Parsed once a process.  ``None``
    without a trace, on a program without a scope table, and on a stale
    executable: a step program none of whose ops stands under a ``ddl.``
    frame was compiled before the scopes were there (a compile cache hit
    across the change; ``ddl_tpu/bringup.py`` salts the key against it),
    and reading it as 100 % unscoped would be a lie."""
    trace = m.get("trace")
    if not trace:
        return None
    want = trace["window_s"]
    if want in _MEMO:
        return _MEMO[want]
    _MEMO[want] = None
    if not _program_scopes():
        _say(refused="the program has no scope table (ops/naming.py:SCOPE_NAMES)")
        return None
    files = glob.glob(os.path.join(
        tempfile.gettempdir(), "ddl_bench_*", "trace", "**", "*.xplane.pb"
    ), recursive=True)
    for path in sorted(files, key=os.path.getmtime, reverse=True):
        t0 = time.monotonic()
        table = tabulate(read_planes(path))
        if table is None or table.window_s != want:
            continue
        parse_s = time.monotonic() - t0
        if not table.n_scoped_ops:
            _say(
                refused="no op of the step program stands under a ddl. frame: "
                "an executable compiled before the scopes (a stale compile "
                "cache entry), not 100 % unscoped", parse_s=parse_s,
            )
            return None
        _say(
            file_bytes=os.path.getsize(path), parse_s=parse_s,
            window_s=table.window_s, summary=table.summary(), rows=rows(table),
        )
        _MEMO[want] = table
        return table
    _say(refused=f"none of {len(files)} trace files has the reduced window")
    return None


def share(m: dict, seconds) -> Optional[float]:
    """``seconds(table)`` as a percentage of the traced window."""
    table = table_of_run(m)
    return None if table is None else 100.0 * seconds(table) / table.window_s


def main(argv: List[str]) -> int:
    if len(argv) != 1:
        print(__doc__.split("\n\n")[0] + "\n\n    python -m benchmarks.lib.scopes "
              "<file.xplane.pb>", file=sys.stderr)
        return 2
    table = tabulate(read_planes(argv[0]))
    if table is None:
        print("the trace holds no two executions of jit__run", file=sys.stderr)
        return 1
    print(render(table))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
