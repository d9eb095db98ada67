"""Which layer of the executor each device operation serves, from the
compiled executor's HLO text.

The program runs every op of its vision executor under a named scope of its
plan's layout (``tokenizer/stage{i}``, ``block{i}/{q,k,v,ssa,attn_lif,proj,
fc1,fc2}``, ``head``), which the compiler keeps as each instruction's
``metadata={op_name=...}``.  A device trace names operations by their HLO
instruction (:func:`traces.op_name`), so :func:`op_scopes` gives the table
from instruction to scope that maps a trace onto the layers.  Instructions
the compiler made (weight copies, layout changes, loops) carry no
``op_name``; two rules attribute them:

* an instruction without a scope takes that of its first consumer that has
  one (copies and layout changes serve what reads them), and where no
  consumer has one, that of its first operand that has one (a prefetch of a
  weight for the next call serves the layer that reads the weight);
* the instructions of a called computation (a loop's body, a fusion's
  computation) take their caller's scope.
"""

from __future__ import annotations

import fnmatch
import re

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLED = re.compile(
    r"\b(?:calls|to_apply|body|condition|true_computation|false_computation)"
    r"=%?([\w.\-]+)|\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_REF = re.compile(r"%?([\w.\-]+)")
_OPCODE = re.compile(r"(?:^|\s)[a-z][\w\-]*\(")
_WRAPPER = re.compile(r"^[^()]*\(.*\)$")       # jit(...), vmap(...), ...


def scope_of(op_name: str) -> str | None:
    """The executor's path of one ``op_name``, without the transformation
    wrappers (``jit(...)``, ``shard_map``) and the primitive at its end:
    ``jit(step)/block3/ssa/pallas_call`` -> ``block3/ssa``.  The compiler
    joins the names of instructions it merges with ``;``; the first that
    has a scope is taken.  None for a name that is no traced path (a
    parameter's name) or has no scope."""
    for name in op_name.split(";"):
        parts = name.split("/")
        if not _WRAPPER.match(parts[0]):
            continue
        path = [p for p in parts[:-1] if not _WRAPPER.match(p) and p != "shard_map"]
        if path:
            return "/".join(path)
    return None


def _computations(hlo_text: str) -> dict[str, list[tuple[str, str]]]:
    """{computation: [(instruction, rest of its line), ...]} in text order
    (an instruction's operands come before it)."""
    comps: dict[str, list[tuple[str, str]]] = {}
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and not line.startswith(("HloModule", " ")):
                current = comps.setdefault(m.group(1), [])
            continue
        if line.strip() == "}":
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m:
            current.append((m.group(1), m.group(2)))
    return comps


def _operands(rest: str) -> list[str]:
    """Names in the operand list of ``<shape> <opcode>(<operands>), ...``.
    The opcode is the first word after a space that opens a parenthesis (a
    shape's tiling, ``{1,0:T(8,128)}``, follows no space); parentheses are
    matched, since an operand's shape may be a tuple."""
    m = _OPCODE.search(rest)
    if m is None:
        return []
    depth = 1
    for i in range(m.end(), len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[i], 0)
        if depth == 0:
            return _REF.findall(rest[m.end():i])
    return []


def _called(rest: str) -> list[str]:
    out = []
    for one, many in _CALLED.findall(rest):
        out += [one] if one else _REF.findall(many)
    return out


def op_scopes(hlo_text: str) -> dict[str, tuple[str | None, bool]]:
    """{instruction: (scope or None, is a Pallas kernel)} for every
    instruction of the compiled module, by the rules of the module
    docstring.  None where no rule finds a scope."""
    comps = _computations(hlo_text)
    callers = {c for rows in comps.values() for _, rest in rows for c in _called(rest)}
    table: dict[str, tuple[str | None, bool]] = {}

    def resolve(comp: str, inherited: str | None) -> None:
        rows = comps.get(comp, [])
        names = {name for name, _ in rows}
        operands = {name: [r for r in dict.fromkeys(_operands(rest))
                           if r in names and r != name] for name, rest in rows}
        users: dict[str, list[str]] = {name: [] for name in names}
        for name, _ in rows:
            for ref in operands[name]:
                users[ref].append(name)
        scope: dict[str, str | None] = {}
        for name, rest in reversed(rows):         # consumers first
            m = _OP_NAME.search(rest)
            scope[name] = (scope_of(m.group(1)) if m else None) or next(
                (scope[u] for u in users[name] if scope[u]), None)
        for name, rest in rows:                   # operands first
            scope[name] = inherited or scope[name] or next(
                (scope[o] for o in operands[name] if scope[o]), None)
            table[name] = (scope[name], KERNEL_TARGET in rest)
            for callee in _called(rest):
                if callee in comps and callee not in done:
                    done.add(callee)
                    resolve(callee, scope[name])

    done: set[str] = set()
    for comp in comps:
        if comp not in callers and comp not in done:
            done.add(comp)
            resolve(comp, None)
    return table


def scope_ns(device, table: dict, pattern: str, lo: float, hi: float, *,
             kernel: bool | None = None) -> float:
    """Device time in [lo, hi) of the operations whose scope matches the
    glob ``pattern`` (``*`` for any scope at all); with ``kernel`` only the
    Pallas kernels (True) or only the rest (False)."""
    total = 0.0
    for name, s, d in device:
        if s + d <= lo or s >= hi:
            continue
        scope, is_kernel = table.get(name, (None, False))
        if scope is None or not fnmatch.fnmatchcase(scope, pattern):
            continue
        if kernel is None or kernel == is_kernel:
            total += min(s + d, hi) - max(s, lo)
    return total

