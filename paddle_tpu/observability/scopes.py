"""Device time by named scope: the one vocabulary of ``jax.named_scope``
names, the rule that books an instruction under one of them, and the
join key between a compiled program's text and a profiler's device event.

A ``jax.named_scope`` is op metadata: it names no instruction and changes
no program. It ends up in the optimized HLO text of the executable
(``..., metadata={op_name="jit(_decode_fn)/decode/attention/cache_read/
dot_general"}``) and NOT in a device trace, whose events are named by the
instruction's text without its metadata. :func:`parse_hlo_scopes` reads
the first, :func:`event_key` keys both, and
``framework.compile_cache.program_scopes`` keeps the maps of the programs
that ran. Stdlib only: the benchmark's readers and ``tools/trace_view.py``
import it beside a trace, the program beside an executable.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

__all__ = ["PROGRAM_KINDS", "BUCKETS", "SUB_SCOPES", "VOCABULARY",
           "UNSCOPED", "INHERITED", "scope_names", "scope_bucket",
           "event_key", "event_opcode", "is_leaf_event", "parse_hlo_scopes"]

#: the outermost scope of a serving program: which KIND of program an
#: instruction is in, kept beside its bucket and never instead of it
PROGRAM_KINDS = ("prefill", "decode")

#: the parts of the model and of the engine that device time is booked
#: under; an instruction's bucket is the INNERMOST of these on its path
BUCKETS = (
    "embed",         # token (and learned position) embeddings
    "block",         # a decoder block's own norms and residual adds
    "attention",     # projections, rotary, scores; less its cache parts
    "cache_write",   # kv_cache.update_kv_cache, whatever implements it
    "cache_read",    # kv_cache.cached_attention / latent_attention
    "mlp",           # a dense FFN
    "moe",           # nn/layers/expert_ffn.py, the shared expert with it
    "mamba",         # a recurrent mixer outside its recurrence
    "scan",          # the recurrence over a block (prefill)
    "state_update",  # the recurrence's one step (decode)
    "streams",       # xing.py's residual streams: read, mix, write back
    "ut_step",       # ouro.py's recurrent pass outside its blocks
    "exit_gate",     # ouro.py's gate (exit_pdf alone: not served)
    "final_norm",    # the norm between the last block and the head
    "lm_head",
    "loss_head",
    "sample",
    "optimizer",
)

#: printed as sub-rows of the bucket they sit in; they never take an
#: instruction away from it
SUB_SCOPES = (
    "mla", "absorb",                                    # attention
    "router", "dispatch", "experts", "combine", "shared_expert",  # moe
    "in_proj", "conv", "ssm_params", "out_proj",        # mamba
    "hc_pre", "sinkhorn", "hc_post",                    # streams
)

#: every ``jax.named_scope`` literal under ``paddle_tpu/`` is one of these
#: (``tests/test_program_scopes.py`` greps them)
VOCABULARY = PROGRAM_KINDS + BUCKETS + SUB_SCOPES

UNSCOPED = "unscoped"
#: between a path an instruction took from a neighbour and that neighbour
#: (:func:`parse_hlo_scopes`)
INHERITED = " <- "

# jvp(attention), transpose(jvp(mlp)), jit(_decode_fn): the transforms'
# wrappers around a path segment
_WRAPPERS = re.compile(r"^(?:\w+\()+|\)+$")


def scope_names(op_name: str) -> list:
    """The segments of an ``op_name`` path with the transforms' wrappers
    taken off, outermost first: ``"jit(f)/transpose(jvp(attention))/mul"``
    -> ``["f", "attention", "mul"]``."""
    return [_WRAPPERS.sub("", seg) for seg in op_name.split("/")]


def scope_bucket(op_name: str) -> Tuple[Optional[str], str, Optional[str]]:
    """``(kind, bucket, sub)`` of an instruction's ``op_name`` path: the
    outermost of :data:`PROGRAM_KINDS` on it (None if none), the innermost
    of :data:`BUCKETS` (:data:`UNSCOPED` if none) and the innermost of
    :data:`SUB_SCOPES` inside that bucket (None if none)."""
    names = scope_names((op_name or "").split(INHERITED)[0])
    kind = next((n for n in names if n in PROGRAM_KINDS), None)
    at = next((i for i in range(len(names) - 1, -1, -1)
               if names[i] in BUCKETS), None)
    if at is None:
        return kind, UNSCOPED, None
    sub = next((n for n in reversed(names[at + 1:]) if n in SUB_SCOPES),
               None)
    return kind, names[at], sub


# ---------------------------------------------------------------- the key
_ATTRS = re.compile(r", (?:metadata|backend_config|frontend_attributes)=")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_NAME = re.compile(r"%[\w.\-]+")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
#: an event of one of these encloses the events of its computation(s):
#: its time is theirs once more
CONTAINERS = ("while", "conditional", "call")


def _split(text: str):
    """``(name, result shape, opcode, operands and attributes)`` of an
    instruction's text, layouts dropped, or None if it is no instruction."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    cut = _ATTRS.search(text)
    if cut:
        text = text[:cut.start()]
    name, sep, rest = text.partition(" = ")
    if not sep or not name.startswith("%"):
        return None
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):    # a tuple's shape: to its closing bracket
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        end += 1
    else:
        end = rest.find(" ")
    m = _OPCODE.match(rest, end) if end > 0 else None
    if m is None:
        return None
    return name, rest[:end], m.group(1), rest[m.end():]


def event_key(text: str) -> Optional[str]:
    """What a device event and the line of the optimized HLO text it came
    from have in common, exactly: the instruction's name, its result's
    shape, its opcode and every name it refers to (operands, ``calls=``,
    ``body=``), in order. The event prints its operands' shapes and the
    text does not; the text ends in ``metadata=``, ``backend_config=`` and
    ``frontend_attributes=`` and the event does not; memory layouts
    (``{1,0:T(8,128)S(1)}``) are dropped from both. None for a line that
    is no instruction."""
    parts = _split(text)
    if parts is None:
        return None
    name, shape, opcode, rest = parts
    return " ".join([name, shape, opcode] + _NAME.findall(rest))


def event_opcode(text: str) -> Optional[str]:
    parts = _split(text)
    return None if parts is None else parts[2]


def is_leaf_event(text: str) -> bool:
    """False for an event that encloses others (:data:`CONTAINERS`)."""
    return event_opcode(text) not in CONTAINERS


_OP_NAME = re.compile(r'op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$")


def parse_hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """``{event_key: op_name}`` for every instruction of an executable's
    optimized HLO text (``Compiled.as_text()``), fused computations'
    included (no event is named after those, so they match nothing).

    An instruction the compiler made itself (a re-layout ``copy``, the
    ``copy-start`` / ``copy-done`` and ``slice-start`` / ``slice-done`` of
    a prefetch, the pieces a scatter's loop is expanded into, a
    ``ragged-dot`` custom call) has no ``op_name`` path: no
    ``named_scope`` can reach it. It takes the path of its nearest
    neighbour that has one, marked ``"<path> <- %neighbour"``: the first
    of its users (a prefetched or re-laid weight belongs to the layer that
    consumes it), else the first of its operands, else the instruction
    that calls its computation (a loop's body belongs to the loop)."""
    instrs, by_name, caller = [], {}, {}
    comp = None
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            m = _COMPUTATION.match(line)
            if m:
                comp = m.group(1)
            continue
        parts = _split(line)
        if parts is None:
            continue
        name, shape, opcode, rest = parts
        refs = _NAME.findall(rest)
        m = _OP_NAME.search(line)
        ins = {"name": name, "op": m.group(1) if m else "", "refs": refs,
               "key": " ".join([name, shape, opcode] + refs), "comp": comp,
               "users": []}
        by_name[name] = ins
        instrs.append(ins)
    for ins in instrs:
        for ref in ins["refs"]:
            if ref in by_name:
                by_name[ref]["users"].append(ins)
            else:               # a computation: this instruction calls it
                caller.setdefault(ref, ins)

    def take(ins, neighbours):
        for other in neighbours:
            if "/" in other["op"]:
                path = other["op"].split(INHERITED)[0]
                ins["op"] = f"{path}{INHERITED}{other['name']}"
                return True
        return False

    for _ in range(4):       # a loop in a loop in a branch: deep enough
        changed = False
        for ins in reversed(instrs):                    # from users
            if "/" not in ins["op"]:
                changed |= take(ins, ins["users"])
        for ins in instrs:                              # from operands
            if "/" not in ins["op"]:
                changed |= take(ins, (by_name[r] for r in ins["refs"]
                                      if r in by_name))
        for ins in instrs:                              # from the caller
            if "/" not in ins["op"] and ins["comp"] in caller:
                changed |= take(ins, [caller[ins["comp"]]])
        if not changed:
            break
    return {ins["key"]: ins["op"] for ins in instrs}
