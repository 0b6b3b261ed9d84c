"""The step programs this process has built, and for each the table
from a device op to the ``jax.named_scope`` it was traced under.

A TPU profiler trace names a device op by its HLO instruction
(``%fusion.12``) and carries no ``op_name``; the scope an op belongs
to (``3_TransformerEncoderLayer/mlp``, ``updater``, ``moe/experts``)
is in the compiled module's text alone. Whoever builds a step program
registers it here the first time it runs (``PagedSlotSession`` each
width of its step, both executors their train step and fused window):
ONE call a program, never one a step. Whoever holds a trace asks
:func:`scope_tables` afterwards.

What a registration keeps is the Python function, its ``jax.jit``
options, the abstract arguments (shape, dtype, and the sharding of a
placed array) and the dtype policy it was traced under: no array, no
loaded executable, and nothing of the object that ran it, so a table
can still be built after the session or network is gone. The text is
compiled only when a table is asked for (under a persistent compile
cache, a load), once a program.
"""

from __future__ import annotations

import re
import threading
from typing import Any, Callable, Dict, List, Optional, Tuple

__all__ = ["ProgramRegistry", "PROGRAMS", "register", "scope_tables",
           "entry_table"]

# one instruction of a computation: ``  [ROOT ]%name = ...``
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")

Table = List[Tuple[str, str]]


def entry_table(hlo_text: str) -> Table:
    """``[(instruction name, op_name), ...]`` of a compiled module's
    ENTRY computation, in the order its text lists them (the schedule
    of a scheduled module); ``op_name`` is "" where the instruction
    carries none. Names are without the ``%``."""
    table, inside = [], False
    for line in hlo_text.splitlines():
        if not inside:
            inside = line.startswith("ENTRY ")
        elif line.startswith("}"):
            break
        else:
            m = _INSTRUCTION.match(line)
            if m:
                op = _OP_NAME.search(line)
                table.append((m.group(1), op.group(1) if op else ""))
    return table


def _abstract(x):
    """Shape, dtype and weak type of one argument; the sharding of an
    array someone placed (an unplaced one goes where the call puts
    it)."""
    import jax
    aval = jax.typeof(x)
    sharding = x.sharding if getattr(x, "committed", False) else None
    return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sharding,
                                weak_type=aval.weak_type)


class _Program:
    __slots__ = ("fn", "jit_kwargs", "avals", "policy", "table")

    def __init__(self, fn, jit_kwargs, avals, policy):
        self.fn, self.jit_kwargs = fn, jit_kwargs
        self.avals, self.policy = avals, policy
        self.table: Optional[Table] = None


def _build(prog: _Program) -> Table:
    import jax
    from deeplearning4j_tpu import dtypes
    with dtypes.policy_scope(prog.policy):
        text = jax.jit(prog.fn, **prog.jit_kwargs).lower(
            *prog.avals).compile().as_text()
    return entry_table(text)


class ProgramRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._building = threading.Lock()
        self._programs: Dict[str, _Program] = {}

    def register(self, name: str, fn: Callable,
                 jit_kwargs: Dict[str, Any], args: tuple) -> None:
        """``fn`` under ``jax.jit(**jit_kwargs)`` is about to run on
        ``args`` for the first time. A name registered again (a
        rebuilt program, another session) replaces the older one."""
        import jax
        from deeplearning4j_tpu import dtypes
        prog = _Program(fn, dict(jit_kwargs),
                        jax.tree_util.tree_map(_abstract, args),
                        dtypes.policy())
        with self._lock:
            self._programs[name] = prog

    def scope_tables(self) -> Dict[str, Table]:
        """``{program name: entry_table of its compiled module}`` for
        every program registered so far. The first call after a
        registration lowers and compiles that program from its
        abstract arguments; later calls compile nothing."""
        # builders take turns; ``_lock`` is not held over a compile,
        # which can take a minute while a step thread registers
        with self._building:
            with self._lock:
                todo = [p for p in self._programs.values()
                        if p.table is None]
            for prog in todo:
                prog.table = _build(prog)
                prog.fn = prog.avals = None
        with self._lock:
            return {name: list(prog.table)
                    for name, prog in self._programs.items()
                    if prog.table is not None}

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()


PROGRAMS = ProgramRegistry()
register = PROGRAMS.register
scope_tables = PROGRAMS.scope_tables
