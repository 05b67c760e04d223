"""Shared by the ``scope_*_share.train`` readers: the traced window's device
seconds by the program's own parts.

The program puts every operation of a step under one of six
``jax.named_scope``s, ``part_<name>``, and a layer walk's three phases under
``walk_fwd`` / ``walk_recompute`` / ``walk_bwd``
(``tepdist_tpu/models/layers.py:PARTS``, ``PHASES``; the names are repeated
here because the readers also run over a program that has none). A trace
event is named by its instruction's HLO text, which carries no scope, and
``jax.profiler.ProfileData`` (jax 0.9.0) hands out an event's own stats only.
The scopes are in the ``.xplane.pb`` all the same: the device plane's
``event_metadata`` map holds, once a distinct operation, a ``tf_op`` stat with
the operation's whole name stack, e.g.
``jit(tepdist_train_step)/while/body/closed_call/walk_bwd/``
``transpose(jvp(part_mixer/mla_q))/dot_general:``
(what xprof groups by as the framework operation). :func:`operation_scopes`
reads that map with a protobuf wire reader that steps over the plane's
``lines`` by their length, so it costs a distinct operation's time and not an
event's, and imports no TensorFlow.

JAX wraps scopes in its transforms' names, so a name is looked for as a whole
word anywhere in the path. A part is the **last** ``part_<name>`` of the path
(the innermost scope), a phase the **first** ``walk_<name>`` (a backward
rule's operations carry ``walk_bwd/transpose(walk_recompute)/...``). One
phase more is JAX's own mark, ``rematted_computation``: what a
``jax.checkpoint`` inside a block makes again in the backward pass. A block
whose token-wise parts run in rematerialised chunks (``over_sequence``) has
their second forward pass there and not under ``walk_recompute``, where
nothing needs their output and JAX drops them, so the two together are a
step's recomputation (``scope_recompute_share.train``). An operation whose
path holds no part is ``unscoped``; a fusion is charged to the path it carries
(its root's). Sub-scopes (``mla_q``, ``ssm_scan``, ...) are whatever words
follow the part that are not JAX's own; they feed the printed table only.

Seconds are ``TraceSummary``'s self seconds by operation (a ``while`` keeps
what its body leaves uncovered), mean over the devices; the shares are of
their sum. Computed once a run (``cell.facts``), printed once a run.
"""

import re

from benchmark import trace_reduce

PARTS = ("embed", "mixer", "mlp", "moe", "head_loss", "optimizer")
PHASES = ("walk_fwd", "walk_recompute", "walk_bwd")
REMATTED = "rematted"                 # JAX's ``rematted_computation``
RECOMPUTED = ("walk_recompute", REMATTED)
UNSCOPED, OUTSIDE = "unscoped", "-"
TF_OP = "tf_op"


def _whole_word(names):
    return re.compile(
        r"(?<![A-Za-z0-9_])(%s)(?![A-Za-z0-9_])" % "|".join(names))


_PART = _whole_word("part_" + p for p in PARTS)
_PHASE = _whole_word(PHASES)
_REMATTED = _whole_word(["rematted_computation"])
_KERNEL = re.compile(r"tepdist_[a-z0-9]+(?:_[a-z0-9]+)*")
_NESTED_JIT = re.compile(r"p?jit\([^()]*\)")
_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
# Path components that are JAX's and no scope of the program's.
_JAX_WORDS = re.compile(
    r"jvp|transpose|vmap|checkpoint|remat|rematted_computation|cond|"
    r"branch_\d+_fun|while|body|scan|closed_call|core_call|shard_map|"
    r"custom_[jv]vp_call\w*|pallas_call")


# -- the .xplane.pb's operation metadata ------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes (a view, not a copy) for a length-delimited or fixed field."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"protobuf wire type {wire} at byte {i}")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def _map_values(entries):
    """The values (field 2) of a protobuf map's entries."""
    for entry in entries:
        for number, value in _fields(entry):
            if number == 2:
                yield value


def operation_scopes(xplane_path: str) -> dict:
    """``{short operation name: tf_op}`` of the device planes' operations,
    ``""`` for one that has none (``trace_reduce.short_name`` of the
    operation's HLO text, the key of ``DeviceSummary.op_self_s``).
    XSpace.planes = 1; XPlane: name 2, lines 3 (stepped over),
    event_metadata 4, stat_metadata 5; XEventMetadata: name 2, stats 5;
    XStatMetadata: id 1, name 2; XStat: metadata_id 1, str_value 5, ref_value
    7 (a stat_metadata id whose name is the string)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    scopes = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, events, stats = "", [], []
        for number, value in _fields(plane):
            if number == 2:
                name = bytes(value).decode()
            elif number == 4:
                events.append(value)
            elif number == 5:
                stats.append(value)
        if not name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        stat_names = {}
        for meta in _map_values(stats):
            found = dict(_fields(meta))
            stat_names[found.get(1, 0)] = bytes(found.get(2, b"")).decode()
        for meta in _map_values(events):
            text, path = "", ""
            for number, value in _fields(meta):
                if number == 2:
                    text = bytes(value).decode()
                elif number == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) != TF_OP:
                        continue
                    path = bytes(stat[5]).decode() if 5 in stat \
                        else stat_names.get(stat.get(7), "")
            scopes.setdefault(trace_reduce.short_name(text), path)
    return scopes


# -- an operation's place in the step ---------------------------------------

def place(path: str) -> tuple:
    """``(part, phase, sub-scope)`` of an operation's ``tf_op``: the part an
    ``unscoped`` and the others ``-`` where the path names none. The
    sub-scope is the innermost of the program's scopes after the part, a
    kernel's name (``tepdist_...``) where the path ends in one."""
    parts = list(_PART.finditer(path))
    phase = _PHASE.search(path)
    phase = REMATTED if _REMATTED.search(path) \
        else phase.group(1) if phase else OUTSIDE
    if not parts:
        return UNSCOPED, phase, OUTSIDE
    tail = path[parts[-1].end():].rsplit("/", 1)[0]
    # ``tepdist_mla_fwd`` of ``tepdist_mla_fwd__c1__s0.135__h16``.
    kernels = _KERNEL.findall(tail)
    words = kernels[-1:] or [
        w for w in _WORD.findall(_NESTED_JIT.sub("", tail))
        if not _JAX_WORDS.fullmatch(w) and not _PART.fullmatch(w)
        and w not in PHASES]
    return (parts[-1].group(1)[len("part_"):], phase,
            words[-1] if words else OUTSIDE)


def by_part(trace, scopes: dict) -> dict:
    """The traced window's self seconds (mean over the devices) ``by_part``
    ``{part: s}``, ``by_phase`` ``{(part, phase): s}``, ``by_sub`` ``{(part,
    sub-scope): s}``, their ``total_s``, and ``unscoped``: ``[(seconds, HLO
    text, tf_op)]`` of the operations under no part, longest first."""
    out = {"by_part": {}, "by_phase": {}, "by_sub": {}}
    unscoped = {}
    n = len(trace.devices)
    for d in trace.devices:
        for op, seconds in d.op_self_s.items():
            path = scopes.get(op, "")
            part, phase, sub = place(path)
            for table, key in (("by_part", part), ("by_phase", (part, phase)),
                               ("by_sub", (part, sub))):
                out[table][key] = out[table].get(key, 0.0) + seconds / n
            if part == UNSCOPED:
                key = d.op_text[op], path
                unscoped[key] = unscoped.get(key, 0.0) + seconds / n
    out["total_s"] = sum(out["by_part"].values())
    out["unscoped"] = sorted(((s, *key) for key, s in unscoped.items()),
                             reverse=True)
    return out


def table(found: dict, longest: int = 10) -> str:
    """What a run prints: part x phase seconds, every sub-scope's seconds
    under its part, the longest unscoped operations."""
    total = found["total_s"]
    phases = (OUTSIDE, "walk_fwd") + RECOMPUTED + ("walk_bwd",)
    lines = ["scopes: device self seconds of the traced window by part and "
             f"phase (total {total:.6f} s)",
             "  %-10s" % "part" + "".join("%16s" % p for p in phases)
             + "%12s%8s" % ("all", "%")]
    for part in PARTS + (UNSCOPED,):
        row = [found["by_phase"].get((part, p), 0.0) for p in phases]
        seconds = found["by_part"].get(part, 0.0)
        lines.append("  %-10s" % part + "".join("%16.6f" % s for s in row)
                     + "%12.6f%8.2f" % (seconds, 100.0 * seconds / total))
    lines.append("scopes: sub-scopes (seconds, % of the total)")
    for (part, sub), seconds in sorted(
            found["by_sub"].items(),
            key=lambda kv: ((PARTS + (UNSCOPED,)).index(kv[0][0]), -kv[1])):
        if part != UNSCOPED:
            lines.append("  %-10s %-32s%12.6f%8.2f" % (
                part, sub, seconds, 100.0 * seconds / total))
    lines.append(f"scopes: the {longest} longest unscoped operations")
    for seconds, text, path in found["unscoped"][:longest]:
        lines.append("  %10.6f  %s  [%s]" % (
            seconds, text[:2 * trace_reduce.MAX_LABEL], path or "no tf_op"))
    return "\n".join(lines)


# -- what the readers share ---------------------------------------------------

def traced(trace, cell):
    """:func:`by_part` of the run's traced window, read and printed once;
    None where the trace names no operation's ``tf_op`` (or the run was not
    traced)."""
    if "scopes" not in cell.facts:
        found = None
        path = cell.facts.get("trace_path")
        if path:
            scopes = operation_scopes(trace_reduce.find_xplane(path))
            if any(scopes.values()):
                found = by_part(trace, scopes)
                print(table(found), flush=True)
        cell.facts["scopes"] = found
    return cell.facts["scopes"]


def _per_cent(trace, cell, seconds_of):
    found = traced(trace, cell)
    if not found or not found["total_s"]:
        return None
    return 100.0 * seconds_of(found) / found["total_s"]


def part_share(trace, cell, part: str):
    """Per cent of the window's self seconds under ``part``."""
    return _per_cent(trace, cell, lambda f: f["by_part"].get(part, 0.0))


def phase_share(trace, cell, phases):
    """Per cent of the window's self seconds in ``phases``, every part's."""
    return _per_cent(trace, cell, lambda f: sum(
        s for (_, p), s in f["by_phase"].items() if p in phases))
