"""Device time by named scope: the join of a reduced trace's operations
(``trace_reduce.reduce_trace``: event text -> count, ns) with the maps
the program keeps of its executables
(``paddle_tpu.framework.compile_cache.program_scopes()``: event key ->
``op_name`` path, per program), booked by the program's one rule
(``paddle_tpu.observability.scopes.scope_bucket``).

What is summed is LEAF time: an event whose opcode encloses others
(``while``, ``conditional``, ``call``) is their time once more and is
left out; the shares' denominator is the sum of the leaves. An event
that no map holds is ``unmatched`` (the little jitted programs between
two steps: a key split, an unstack); one that two programs hold and book
differently is split by how often each ran (:func:`join`), or
``ambiguous`` where neither is known to have run. Over 1 % of the leaves
in those two together and every reader returns None: the join is broken
or the text is stale, and no number is better than a wrong one. A program without ``program_scopes`` (the parent of the PR
that brought it) gives None too, and the metric is left out of the line.
"""
from __future__ import annotations

import time
from collections import defaultdict

#: leaves found in no map, or booked two ways, may be this share of all
#: leaves before the readers give up
UNMATCHED_LIMIT = 0.01
#: unscoped instructions over this share of the leaves are listed
LISTED_SHARE = 0.005
UNMATCHED, AMBIGUOUS = "unmatched", "ambiguous"


def _program_side():
    """(program_scopes, scopes module) or None where the program has
    neither."""
    try:
        from paddle_tpu.framework.compile_cache import program_scopes
        from paddle_tpu.observability import scopes
    except ImportError:
        return None
    return program_scopes, scopes


def _runs_by_module(devices: list) -> dict:
    """How often each program ran in the slice, by its name less the hash
    (``jit__decode_fn(123)`` -> ``jit__decode_fn``), over all chips."""
    runs = defaultdict(int)
    for dev in devices:
        for name, _, _ in dev.get("modules", ()):
            runs[name.split("(", 1)[0]] += 1
    return runs


def join(devices: list, maps: dict, scopes) -> dict:
    """``devices`` as ``reduce_trace`` gives them, ``maps`` as
    ``program_scopes()`` does. Returns ``{"leaves_s", "containers_s",
    "rows": {(kind, bucket, sub): s}, "listed": {(kind, bucket, event
    text): (s, op_name)}, "inherited_s", "shared_s"}``. Seconds are
    averaged over the chips that ran anything, as ``busy_s`` is.

    An event whose text two programs hold and book differently (the
    decode program and a prefill program re-lay the same weight under the
    same instruction name) cannot be told apart in a reduced trace, which
    keeps no event's program: it is split between them in proportion to
    how often each one's program ran in the slice, exact where only one of
    them ran; ``shared_s`` is what was split so. With no run of either on
    record it is ``ambiguous`` (``kind`` None, as for ``unmatched``).
    ``inherited_s`` is the time booked through a neighbour's scope
    (``scopes.INHERITED``)."""
    index = defaultdict(dict)  # key -> {(kind, bucket, sub): [op_name, runs]}
    runs = _runs_by_module(devices)
    for prog in maps.values():
        ran = runs.get(prog.get("module"), 0)
        for key, op_name in prog["ops"].items():
            _, bucket, sub = scopes.scope_bucket(op_name)
            slot = index[key].setdefault((prog["kind"], bucket, sub),
                                         [op_name, 0])
            slot[1] = max(slot[1], ran)
    chips = max(1, sum(1 for d in devices if d["busy_ns"] > 0))
    rows, listed = defaultdict(float), {}
    leaves = containers = inherited = shared = 0.0

    def book(row, op_name, sec, text):
        nonlocal inherited
        rows[row] += sec
        if scopes.INHERITED in op_name:
            inherited += sec
        if row[1] in (scopes.UNSCOPED, UNMATCHED, AMBIGUOUS):
            at = (row[0], row[1], text)
            listed[at] = (listed.get(at, (0.0, ""))[0] + sec, op_name)

    for dev in devices:
        for text, (_, ns) in dev["ops"].items():
            sec = ns * 1e-9 / chips
            if not scopes.is_leaf_event(text):
                containers += sec
                continue
            leaves += sec
            found = index.get(scopes.event_key(text))
            if not found:
                book((None, UNMATCHED, None), "", sec, text)
                continue
            if len(found) == 1:
                (row, (op_name, _)), = found.items()
                book(row, op_name, sec, text)
                continue
            ran = {row: slot for row, slot in found.items() if slot[1]}
            if not ran:
                book((None, AMBIGUOUS, None), "", sec, text)
                continue
            if len(ran) > 1:
                shared += sec
            total = sum(n for _, n in ran.values())
            for row, (op_name, n) in ran.items():
                book(row, op_name, sec * n / total, text)
    return {"leaves_s": leaves, "containers_s": containers,
            "rows": dict(rows), "listed": listed,
            "inherited_s": inherited, "shared_s": shared}


def lines(table: dict, busy_s=None, op_key=lambda text: text[:160]) -> list:
    """The whole table as text: every kind and bucket with seconds and
    share of the leaves, sub-scopes under their bucket, the unscoped,
    unmatched and ambiguous instructions over ``LISTED_SHARE``."""
    total = table["leaves_s"]
    if total <= 0:
        return ["device time by scope: no leaf event in the slice"]
    out = [f"device time by scope: leaves {total:.4f} s"
           + (f" against busy {busy_s:.4f} s ({100 * (total / busy_s - 1):+.2f}"
              f" %)" if busy_s else "")
           + f"; enclosing events left out {table['containers_s']:.4f} s; "
           f"booked through a neighbour's scope (no op_name of their own) "
           f"{table['inherited_s']:.4f} s; split between two programs by "
           f"their runs {table['shared_s']:.4f} s"]
    by_bucket = defaultdict(float)
    for (kind, bucket, _), sec in table["rows"].items():
        by_bucket[(kind, bucket)] += sec
    for (kind, bucket), sec in sorted(by_bucket.items(),
                                      key=lambda kv: -kv[1]):
        out.append(f"  {sec:9.4f} s {100 * sec / total:6.2f} %  "
                   f"{kind or '-'} / {bucket}")
        subs = [(sub, s) for (k, b, sub), s in table["rows"].items()
                if (k, b) == (kind, bucket) and sub is not None]
        for sub, s in sorted(subs, key=lambda kv: -kv[1]):
            out.append(f"  {s:9.4f} s {100 * s / total:6.2f} %      "
                       f"{bucket} / {sub}")
    grouped = defaultdict(float)
    for (kind, bucket, text), (sec, op_name) in table["listed"].items():
        grouped[(kind, bucket, op_key(text), op_name)] += sec
    big = [(k, sec) for k, sec in grouped.items()
           if sec > LISTED_SHARE * total]
    if big:
        out.append(f"  instructions over {100 * LISTED_SHARE} % of the "
                   f"leaves in no bucket:")
    for (kind, bucket, key, op_name), sec in sorted(big,
                                                    key=lambda kv: -kv[1]):
        out.append(f"  {sec:9.4f} s {100 * sec / total:6.2f} %  "
                   f"{kind or '-'} / {bucket}: {key}  [{op_name}]")
    return out


def table(ctx: dict):
    """The joined table of this run's traced slice, made and logged once
    (kept in ``ctx``), or None: no program side, no map, or too much of
    the slice in no map."""
    if "scope_table" in ctx:
        return ctx["scope_table"]
    ctx["scope_table"] = None
    side = _program_side()
    if side is None or not ctx.get("trace"):     # no trace: not a traced run
        return None
    program_scopes, scopes = side
    t0 = time.perf_counter()
    maps = program_scopes()
    t1 = time.perf_counter()
    if not maps:
        ctx["log"]("device time by scope: the program kept no executable")
        return None
    tb = join(ctx["trace"]["devices"], maps, scopes)
    kept_s = sum({name.split("@")[0]: m.get("keep_s", 0.0)
                  for name, m in maps.items()}.values())
    ctx["log"](f"scope maps of {len(maps)} executables "
               f"({sum(len(m['ops']) for m in maps.values())} instructions): "
               f"kept in {kept_s:.3f} s while the programs warmed up, parsed "
               f"in {t1 - t0:.2f} s, joined in "
               f"{time.perf_counter() - t1:.2f} s")
    for line in lines(tb, ctx["trace"]["busy_s"],
                      ctx["trace_reduce"].op_key):
        ctx["log"](line)
    lost = sum(sec for (kind, bucket, _), sec in tb["rows"].items()
               if kind is None)
    if tb["leaves_s"] <= 0 or lost > UNMATCHED_LIMIT * tb["leaves_s"]:
        ctx["log"](f"device time by scope: {lost:.4f} s of "
                   f"{tb['leaves_s']:.4f} s in no map or booked two ways "
                   f"(limit {100 * UNMATCHED_LIMIT} %): no scope metric")
        return None
    ctx["scope_table"] = tb
    return tb


def share(ctx: dict, buckets=None, kind=None, other_than_kind=None,
          absent=None):
    """Percent of the slice's leaf device time in the rows that match:
    ``buckets`` (None: any), of programs of ``kind`` (None: any), or of
    everything that is NOT of ``other_than_kind`` (the leaves no map
    holds among it). ``absent`` where no row matches (None: a cell whose
    model has no such scope reports nothing); None where :func:`table`
    is."""
    tb = table(ctx)
    if tb is None:
        return None
    hit = [sec for (k, bucket, _), sec in tb["rows"].items()
           if (buckets is None or bucket in buckets)
           and (kind is None or k == kind)
           and (other_than_kind is None or k != other_than_kind)]
    return 100.0 * sum(hit) / tb["leaves_s"] if hit else absent
