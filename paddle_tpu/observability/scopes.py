"""The named parts of a model, and which part each compiled operation is.

ONE list of names (`NAMES`): the models and the train step name their parts
with `scope(name)`, a `jax.named_scope` that changes HLO metadata only. Each
instruction of a compiled program then carries its path in
`metadata={op_name="jit(_step_fn)/transpose(jvp(loss))/mlp/moe_experts/..."}`.
An instruction belongs to the INNERMOST listed name on that path, in the
forward and the backward pass alike (`jvp(...)`, `transpose(jvp(...))`,
`checkpoint`); names that are not on the list (`loss`, `mla_rope`) are read
through. An `op_name` that joins several paths with `;` is read by its first.

A device trace names an operation by its HLO instruction (`%fusion.374 =
...`), never by that path. `CompiledTrainStep` therefore publishes, once for
each program it builds, a table from instruction name to part (`publish`):
the benchmark joins the trace's `XLA Ops` events against `last_table()`.
The table holds plain strings only, nothing of the program or its arrays.

Only instructions that run as operations of their own are in the table: those
of the entry computation and, recursively, of the bodies, conditions and
branches of control flow and of `call`s. The computations inside a fusion or
applied by a reduction run inside one operation and have no event. An
instruction takes the part of the computation it calls where its own path
names none (a fusion's or a loop's, read from that computation's
instructions, the root first). One that XLA made (no path of its own: no
`op_name`, or a bare one such as `gather`) takes the part of its first operand
that has one (a `copy-done` is its `copy-start`'s, an output's copy the
value's; a loop body's parameter is the loop's), else of its first user that
has one (a weight's prefetch is the part that reads the weight), else of the
loop or call it runs in, else it is `UNSCOPED`.
"""
from __future__ import annotations

import re

__all__ = ["NAMES", "UNSCOPED", "scope", "part_of", "table", "publish",
           "last_table"]

NAMES = ("embed", "attn", "kda", "conv_mixer", "mlp", "moe_router",
         "moe_layout", "moe_experts", "moe_shared", "head", "optimizer")
UNSCOPED = "unscoped"

_last: dict | None = None


def scope(name: str):
    """`jax.named_scope(name)` for a name on the list."""
    import jax

    if name not in NAMES:
        raise ValueError(f"{name!r} is not one of the named parts {NAMES}")
    return jax.named_scope(name)


def part_of(op_name: str) -> str:
    """The innermost listed name on an `op_name` path, or `UNSCOPED`."""
    found = UNSCOPED
    for word in re.findall(r"\w+", op_name.split(";")[0]):
        if word in NAMES:
            found = word
    return found


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLEES = re.compile(r"\b(?:condition|body|to_apply|calls|true_computation|"
                      r"false_computation|branch_computations)=\{?([^}\s,]+(?:, *%[\w.\-]+)*)")
_OPCODE = re.compile(r" ([\w\-]+)\(")
_NAME = re.compile(r"%([\w.\-]+)")
# computations that run as operations of their own when these call them
_CONTROL = ("while", "conditional", "call", "async-start")


def _computations(text: str):
    """(module name, entry computation, {computation: [(name, root?, text
    after ` = `), ...]}), the instructions in the text's order."""
    module, entry, comps, cur = None, None, {}, None
    for line in text.splitlines():
        if line.startswith(" "):
            m = _INSTRUCTION.match(line)
            if m and cur is not None:
                cur.append((m.group(2), bool(m.group(1)), m.group(3)))
        elif module is None and line.startswith("HloModule "):
            module = line.split()[1].rstrip(",")
        else:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
                if line.startswith("ENTRY"):
                    entry = m.group(1)
    return module, entry, comps


def _details(rest: str):
    """(opcode, op_name or None, [callees], [operands]) of an instruction's
    text after ` = `. The opcode is the first word followed by `(` after a
    space (a type holds none: `bf16[8]{0:T(1024)}`, `(f32[], s32[])`); every
    other `%name` is an operand or a callee."""
    op = _OPCODE.search(rest)
    meta = _OP_NAME.search(rest)
    callees = [c.strip().lstrip("%") for group in _CALLEES.findall(rest)
               for c in group.split(",")]
    operands = [n for n in _NAME.findall(rest) if n not in callees]
    return (op.group(1) if op else "", meta.group(1) if meta else None,
            callees, operands)


def table(text: str) -> dict:
    """{"module": the HLO module's name, "ops": {instruction name: part}} of a
    compiled program's text (`Compiled.as_text()`)."""
    module, entry, comps = _computations(text)
    memo: dict[str, str] = {}

    def own(op_name, callees, seen) -> str:
        """The part an instruction's own path names, else the part of the
        computation it calls."""
        found = part_of(op_name) if op_name is not None else UNSCOPED
        for callee in callees if found == UNSCOPED else ():
            found = of_computation(callee, seen)
            if found != UNSCOPED:
                break
        return found

    def of_computation(name: str, seen: frozenset) -> str:
        if name in memo:
            return memo[name]
        if name in seen or name not in comps:
            return UNSCOPED
        found = UNSCOPED
        for _, _, rest in sorted(comps[name], key=lambda ins: not ins[1]):  # the root first
            _, op_name, callees, _ = _details(rest)
            found = own(op_name, callees, seen | {name})
            if found != UNSCOPED:
                break
        memo[name] = found
        return found

    ops: dict[str, str] = {}
    known: dict[str, str] = {}       # ops and the parameters of their computations
    todo, done = [(entry, UNSCOPED)], set()
    while todo:
        comp, caller = todo.pop()
        if comp in done or comp not in comps:
            continue
        done.add(comp)
        parsed = [(name, *_details(rest)) for name, _, rest in comps[comp]]
        made = []                    # XLA's own instructions: no path of their own
        for name, opcode, op_name, callees, operands in parsed:
            if opcode == "parameter":       # a loop's state is the loop's
                known[name] = caller
                continue
            part = own(op_name, callees, frozenset())
            if part == UNSCOPED and (op_name is None or "/" not in op_name):
                part = next((known[o] for o in operands
                             if known.get(o, UNSCOPED) != UNSCOPED), UNSCOPED)
                made.append(name)
            ops[name] = known[name] = part
        users: dict[str, list] = {}
        for name, *_, operands in parsed:
            for o in operands:
                users.setdefault(o, []).append(name)
        for name in reversed(made):  # a prefetch or a layout copy: its user's
            if ops[name] == UNSCOPED:
                ops[name] = known[name] = next(
                    (ops[u] for u in users.get(name, ()) if ops.get(u, UNSCOPED) != UNSCOPED),
                    caller)
        for name, opcode, _, callees, _ in parsed:
            if opcode in _CONTROL:
                todo.extend((callee, ops[name]) for callee in callees)
    return {"module": module, "ops": ops}


def publish(text: str) -> dict:
    """Make the table of a compiled program's text the one `last_table()`
    returns, and return it."""
    global _last
    _last = table(text)
    return _last


def last_table() -> dict | None:
    """The table of the program built last in this process, or None."""
    return _last
