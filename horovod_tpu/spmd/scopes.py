"""Device time by scope, from the program: which named scope each
device op of a compiled step belongs to.

A ``jax.named_scope`` (and a flax module's name) is a component of the
``op_name`` that every instruction of an executable carries in its
metadata, ``jit(step)/shard_map/loss/jvp(TransformerLM)/block_0/attn/
q/dot_general``. A profile names a device event by its HLO instruction
(``fusion.2031``) and keeps the scope where
``jax.profiler.ProfileData`` does not show it, so the executable is
where code reads it: :func:`device_scopes` maps each instruction that
can appear as a device event to the innermost component of its path
that is in the vocabulary (``trace.DEVICE_SCOPES``), and
:func:`scope_of` looks an event up by the name a profile gives it.

Under an armed trace the jitted steps of ``models/train_steps.py`` note
the executable they compile (:func:`note_compiled`, a weak reference);
:func:`noted_device_scopes` builds the table of the one noted last
when it is first asked, so ``as_text()`` and the parse run behind the
measured window. With tracing off nothing is noted, read or parsed.
docs/tracing.md has the vocabulary and an operator's recipe.
"""

from __future__ import annotations

import re
import weakref
from typing import Dict, Iterable, Optional

from horovod_tpu.common.trace import DEVICE_SCOPES
from horovod_tpu.spmd.overlap import _INSTR, _computations

EXCHANGE = "exchange"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# the computations whose instructions are device events of their own:
# a `while`'s body and condition, a conditional's branches, a call's
# callee. A fusion's `calls=` is one event, and a reducer's `to_apply=`
# (of a reduce, a sort, a scatter, an all-reduce) none at all.
_NESTED = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)="
    r"%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CALLEE = re.compile(r"\b(?:to_apply|calls)=%?([\w.\-]+)")
_WRAPPER = re.compile(r"^([\w.\-]+)\((.*)\)$")
_COLLECTIVES = ("all-reduce", "reduce-scatter", "all-gather",
                "collective-permute", "all-to-all")


class ScopeTable(dict):
    """``{instruction name: scope}`` (``""`` = no scope of the
    vocabulary's on its path); ``backward`` holds the names whose path
    lies under a ``transpose(``, which is what autodiff's backward pass
    runs (a recomputed forward among it); ``text_bytes`` is the length
    of the executable's text the table was read from."""

    def __init__(self, scopes=(), backward: Iterable[str] = (),
                 text_bytes: int = 0):
        super().__init__(scopes)
        self.backward = frozenset(backward)
        self.text_bytes = text_bytes


def _path_scopes(op_name: str, vocabulary, cache: dict):
    """``(scopes, under a transpose)`` of one ``op_name`` path: its
    components that are in ``vocabulary``, outermost first, the
    wrappers JAX adds taken off (``jvp(lm_head_loss)`` is
    ``lm_head_loss``; a ``jit(f)`` names a function, not a scope)."""
    found = cache.get(op_name)
    if found is None:
        scopes, backward = [], False
        for part in op_name.split("/"):
            wrapped = _WRAPPER.match(part)
            while wrapped:
                backward = backward or wrapped.group(1) == "transpose"
                part = "" if wrapped.group(1) in ("jit", "pjit") \
                    else wrapped.group(2)
                wrapped = _WRAPPER.match(part)
            if part in vocabulary:
                scopes.append(part)
        found = cache[op_name] = (tuple(scopes), backward)
    return found


def _shared(found):
    """What every ``(scopes, backward)`` of ``found`` shares: the
    scopes they all open, in order, and whether all are backward."""
    found = list(found)
    if not found:
        return (), False
    shared = found[0][0]
    for scopes, _ in found[1:]:
        n = 0
        while n < min(len(shared), len(scopes)) and shared[n] == scopes[n]:
            n += 1
        shared = shared[:n]
    return shared, all(b for _, b in found)


def device_scopes(compiled, vocabulary=DEVICE_SCOPES) -> ScopeTable:
    """``{instruction name: scope}`` for every instruction of a compiled
    step that can appear as a device event: the entry computation's,
    ``while`` bodies' and conditions', conditional branches', called
    computations' (``compiled`` is what ``jit(...).lower(...)
    .compile()`` returns, or its ``as_text()``). Names are as the text
    has them (``fusion.2031``, ``flash_fwd.12``, ``psum.797``).

    The scope is the innermost component of the instruction's
    ``op_name`` that is in ``vocabulary``. An instruction the compiler
    left without a path takes its place from what is around it: a
    fusion its fused computation's root's path; a ``while``, a
    conditional or a call the scopes all the paths in the computations
    it runs share; one that carries its own name for a path (the
    grouped products' custom calls, ``op_name="ragged-dot-none"``) the
    scopes all the paths of its own computation share; one with no
    metadata at all (a copy, a buffer's fill or a sort the compiler
    made) the scopes its nearest users with a path share, for it is
    their work, where it has none its nearest operands', where none
    either its computation's. A collective, or an ``async-collective-start``/
    ``-done`` fusion, on whose own path no scope lies is ``exchange``;
    nothing found is ``""``."""
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    comps = _computations(text)
    cache: dict = {}
    shared_by: dict = {}
    table, backward = {}, set()

    def path_of(line: str) -> Optional[str]:
        own = _OP_NAME.search(line)
        # a path has components: the compiler's own calls carry their
        # name alone (`op_name="ragged-dot-none"`)
        return own.group(1) if own and "/" in own.group(1) else None

    def root_path(comp: str, depth: int = 0) -> Optional[str]:
        for line in comps.get(comp, ()):
            if line.lstrip().startswith("ROOT"):
                callee = _CALLEE.search(line)
                if path_of(line) or not callee or depth > 4:
                    return path_of(line)
                return root_path(callee.group(1), depth + 1)
        return None

    def shared(names):
        """What the paths in the computations ``names`` share."""
        key = tuple(sorted(names))
        if key not in shared_by:
            shared_by[key] = _shared(
                _path_scopes(path, vocabulary, cache)
                for name in key for line in comps.get(name, ())
                for path in [path_of(line)] if path)
        return shared_by[key]

    def nearest(name, edges, found):
        """What the nearest instructions with a path share, going from
        ``name`` along ``edges`` through those that have none; ``None``
        where there is none within reach."""
        reached, seen, todo = [], {name}, [name]
        while todo and len(seen) < 64:
            for other in edges.get(todo.pop(0), ()):
                if other in seen or other not in found:
                    continue
                seen.add(other)
                if found[other] is None:
                    todo.append(other)
                else:
                    reached.append(found[other])
        return _shared(reached) if reached else None

    seen, todo = set(), ["ENTRY"]
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        found, operands, users = {}, {}, {}
        for line in comps.get(comp, ()):
            m = _INSTR.match(line)
            if not m:
                continue
            name, _, opcode, rest = m.groups()
            operands[name] = re.findall(r"%([\w.\-]+)",
                                        rest.split("), ")[0])
            for operand in operands[name]:
                users.setdefault(operand, []).append(name)
            callee = _CALLEE.search(line)
            nested = _NESTED.findall(line)
            for group in _BRANCHES.findall(line):
                nested += [b.strip().lstrip("%") for b in group.split(",")]
            if callee and opcode in ("call", "async-start"):
                nested.append(callee.group(1))
            todo += nested
            collective = opcode.startswith(_COLLECTIVES) or \
                name.startswith("async-collective-")
            path = path_of(line)
            if path is None and opcode == "fusion" and callee \
                    and not collective:
                path = root_path(callee.group(1))
            if path:
                found[name] = _path_scopes(path, vocabulary, cache)
            elif nested or _OP_NAME.search(line):
                found[name] = shared(nested or [comp])
            else:
                found[name] = None
            if collective and not (found[name] and found[name][0]):
                found[name] = ((EXCHANGE,), False)
        for name, got in found.items():
            if got is None:     # found stays as it was: no chains of guesses
                got = nearest(name, users, found)
                if got is None:
                    got = nearest(name, operands, found)
                if got is None:
                    got = shared([comp])
            table[name] = got[0][-1] if got[0] else ""
            if got[1]:
                backward.add(name)
    return ScopeTable(table, backward, len(text))


def scope_of(table: Dict[str, str], event_name: str) -> Optional[str]:
    """The scope of a device event by the name a profile shows it
    under: ``flash_fwd.12[tpu_custom_call]``, ``psum.797[all-reduce]``,
    ``%fusion.2031 = f32[...] fusion(...)`` or the bare instruction
    name. ``None`` for an instruction the table does not know (an
    event of another executable)."""
    name = event_name.strip()
    if name.startswith("%"):
        name = name[1:].split(" ", 1)[0]
    return table.get(name.split("[", 1)[0])


# -- the step compiled last under an armed trace ---------------------------

_noted = None           # weakref to the executable, then its table


def note_compiled(compiled) -> None:
    """Keep ``compiled`` (weakly) as the step whose table
    :func:`noted_device_scopes` gives. Called by the jitted steps of
    ``models/train_steps.py`` under an armed trace, at no cost but the
    reference: the text is read when the table is first asked for."""
    global _noted
    _noted = weakref.ref(compiled)


def noted_device_scopes() -> Optional[ScopeTable]:
    """The table of the executable noted last, built on the first ask
    and kept; ``None`` where none was noted (tracing off, or no step
    went through ``lower().compile()``) or it is gone."""
    global _noted
    if isinstance(_noted, weakref.ref):
        compiled = _noted()
        _noted = None if compiled is None else device_scopes(compiled)
    return _noted


def _forget_noted_for_tests() -> None:
    global _noted
    _noted = None
