"""pallaslint: the in-kernel DMA/semaphore/VMEM discipline rules.

PR 8's review pass found five chip-only bugs in the fused ring kernels
**by hand**: a re-waited send semaphore (deadlock at size>=3), a gather
write landing in a still-live reduce-scatter recv slot, a VMEM
overflow, a shared ``collective_id`` between concurrent kernels, and a
dtype-discipline hole. All five are invisible in interpret mode —
jax's dma-discharge interpreter serializes DMAs and leaves semaphores
inert — and all five are exactly the class that kills scarce chip
sessions. This module makes them machine-checkable at review time,
the same move jaxlint (PR 4) and shardlint (PR 6) made for Python-level
and SPMD-level hazards.

The centerpiece is a **semaphore-ledger abstract interpreter** over
kernel-body functions (still pure stdlib ``ast`` — analyzed code is
never imported). Kernel bodies are discovered from ``pl.pallas_call``
sites (through ``functools.partial`` wrappers and kernel-factory
functions), then executed abstractly:

- refs (parameters, ``run_scoped`` scratch, unpacked ``*refs``) are
  symbolic; ``ref.at[i]``/``ref[i]`` with concrete ``i`` are slots;
- ``make_async_copy``/``make_async_remote_copy`` build DMA records;
  ``.start()`` adds one outstanding signal per semaphore channel,
  ``.wait()``/``.wait_send()``/``.wait_recv()`` consume the oldest —
  per ``(semaphore, slot)``, so the wait-through-a-fresh-descriptor
  pattern (``get_dma(slot, i).wait()``) accounts correctly;
- Python ring loops unroll; opaque trip counts (the ring ``size``)
  are modeled at :data:`MODEL_RING` devices — the smallest size where
  the PR 8 drain bug manifests is 3, and the model covers it;
- opaque branch predicates fork the analysis (one consistent
  true/false assignment per path, capped); a construct the interpreter
  cannot order soundly makes the kernel **abstain** — no findings,
  never a guess.

Rules (fixtures: ``tests/fixtures/analysis/bad_/clean_pallas_dma.py``,
``bad_/clean_vmem_budget.py``):

- ``dma-sem-balance``   — a wait on a semaphore slot with no
                          outstanding signal (the PR 8 drain
                          double-wait: a slot-reuse wait already
                          consumed it — deadlock on chip), and DMA
                          signals left outstanding at kernel exit
                          (the DMA outlives the kernel's scratch);
- ``dma-slot-reuse``    — a buffer slot rewritten (locally or by a
                          landing DMA) while an un-waited DMA still
                          reads or writes it, and one scratch buffer
                          receiving DMAs under two semaphore families
                          (the PR 8 gather-into-``rs_recv`` shape:
                          dedicated-slot discipline, checkable);
- ``collective-id-collision`` — a hand-picked integer
                          ``collective_id`` (must come from the
                          ``ops.tiling.collective_id`` registry), or
                          two call sites sharing one id/registry name;
- ``kernel-dtype-cast`` — a widened matmul
                          (``preferred_element_type=...``) stored into
                          a kernel ref without ``.astype(ref.dtype)``
                          — interpret mode forgives the implicit
                          cast; Mosaic need not;
- ``vmem-budget``       — a kernel whose literal-resolvable BlockSpec
                          blocks + scratch exceed its
                          ``vmem_limit_bytes`` (estimator:
                          ``analysis/vmem.py``; the symbolic/model
                          side is ``--vmem-report``).
"""

from __future__ import annotations

import ast
from typing import Iterable

from hpc_patterns_tpu.analysis.core import (
    AnalysisConfig,
    Finding,
    ModuleInfo,
    Rule,
    register,
)
from hpc_patterns_tpu.analysis import vmem as vmem_mod

#: modeled ring size for opaque loop bounds (``range(1, size)`` where
#: ``size`` is a runtime mesh axis size). 4 is the smallest even size
#: strictly above the PR 8 drain bug's manifestation threshold (3), so
#: both parities of the alternating send slot are exercised.
MODEL_RING = 4

_PATH_CAP = 64        # max forked paths per kernel before abstaining
_STEP_CAP = 200_000   # abstract-interpreter step budget per path
_DEPTH_CAP = 16       # inline depth for helper calls

_DMA_BUILDERS = frozenset({"make_async_copy", "make_async_remote_copy"})
_DMA_WAITS = frozenset({"wait", "wait_send", "wait_recv"})


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------


class _Opaque:
    """An unresolvable value (runtime data, jnp results, mesh sizes)."""

    __slots__ = ("label",)

    def __init__(self, label: str = "?"):
        self.label = label

    def __repr__(self):  # pragma: no cover - debug aid
        return f"<? {self.label}>"


class _Ref:
    """A kernel ref (operand, output, scratch buffer, or semaphore)."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name


class _AtProxy:
    __slots__ = ("ref",)

    def __init__(self, ref: _Ref):
        self.ref = ref


class _AbsTuple:
    """The ``*refs`` parameter tuple: unknown length; slicing keeps the
    abstraction, unpacking materializes fresh refs named by target."""

    __slots__ = ("prefix",)

    def __init__(self, prefix: str):
        self.prefix = prefix


class _Func:
    __slots__ = ("fndef", "closure")

    def __init__(self, fndef, closure=None):
        self.fndef = fndef
        self.closure = closure or {}


class _Partial:
    __slots__ = ("func", "args", "kwargs")

    def __init__(self, func, args, kwargs):
        self.func = func
        self.args = args
        self.kwargs = kwargs


class _When:
    __slots__ = ("cond",)

    def __init__(self, cond):
        self.cond = cond


class _Method:
    __slots__ = ("obj", "attr")

    def __init__(self, obj, attr):
        self.obj = obj
        self.attr = attr


class _DMA:
    """One async copy: semaphore channels + src/dst slots."""

    __slots__ = ("src", "dst", "send_key", "recv_key", "remote",
                 "node", "start_node", "send_waited", "recv_waited",
                 "started")

    def __init__(self, src, dst, send_key, recv_key, remote, node):
        self.src = src            # (ref_name, idx) or None
        self.dst = dst
        self.send_key = send_key  # (sem_name, idx) or None
        self.recv_key = recv_key
        self.remote = remote
        self.node = node
        self.start_node = None
        self.send_waited = False
        self.recv_waited = False
        self.started = False

    def start_line(self) -> int:
        node = self.start_node or self.node
        return getattr(node, "lineno", 0)


class _Abstain(Exception):
    """The kernel contains a construct the interpreter cannot order
    soundly (opaque semaphore slot, DMA under an unresolvable loop):
    drop every finding for this kernel rather than guess."""


class _NeedFork(Exception):
    def __init__(self, key: str):
        self.key = key


class _Return(Exception):
    def __init__(self, value):
        self.value = value


def _slot_of(value) -> tuple[str, object] | None:
    """(ref_name, idx) of a slot-ish value; idx is an int, ``"*"``
    (whole ref) or ``"?"`` (unresolvable index)."""
    if isinstance(value, _Ref):
        return (value.name, "*")
    if isinstance(value, tuple) and len(value) == 2 and isinstance(
            value[0], str):
        return value
    return None


def _overlaps(a, b) -> bool:
    """Conservative slot overlap: same ref and (either side whole, or
    equal concrete indices). Opaque indices never overlap — precision
    over recall, so model-limit noise can't fake findings."""
    if a is None or b is None or a[0] != b[0]:
        return False
    ia, ib = a[1], b[1]
    if ia == "?" or ib == "?":
        return False
    return ia == "*" or ib == "*" or ia == ib


# ---------------------------------------------------------------------------
# kernel-body discovery
# ---------------------------------------------------------------------------


def _kernel_roots(mod: ModuleInfo) -> list[ast.FunctionDef]:
    """Kernel-body functions reachable from the module's
    ``pallas_call`` sites, deduped in source order."""
    roots: list[ast.FunctionDef] = []
    seen: set[int] = set()
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        if (mod.resolve(node.func) or "").rsplit(".", 1)[-1] != \
                "pallas_call":
            continue
        if not node.args:
            continue
        for fn in vmem_mod.resolve_kernel_arg(mod, node.args[0], node):
            if id(fn) not in seen:
                seen.add(id(fn))
                roots.append(fn)
    return sorted(roots, key=lambda f: f.lineno)


# ---------------------------------------------------------------------------
# the ledger interpreter
# ---------------------------------------------------------------------------


class _KernelRun:
    """One abstract execution of one kernel body under one branch-memo
    assignment. The driver re-runs from the top for each fork."""

    def __init__(self, mod: ModuleInfo, memo: dict[str, bool]):
        self.mod = mod
        self.memo = memo
        self.module_env = self._module_env()
        self.steps = 0
        self._stack: list[str] = []
        # ledger: (sem_name, idx) -> outstanding signal count
        self.ledger: dict[tuple[str, object], int] = {}
        # start nodes per outstanding key, oldest first (exit findings
        # anchor at the start that was never drained)
        self.ledger_nodes: dict[tuple[str, object], list[ast.AST]] = {}
        self.inflight: list[_DMA] = []
        # dst buffer -> recv semaphore names seen (cross-phase rule)
        self.recv_sems_by_buf: dict[str, dict[str, ast.AST]] = {}
        self.findings: list[tuple[str, ast.AST, str]] = []
        # per-subject equality state for mode-switch predicates:
        # name -> (pinned constant | None, excluded constants)
        self._eq_state: dict[str, tuple[object, set]] = {}

    # -- environment -----------------------------------------------------

    def _module_env(self) -> dict[str, object]:
        env: dict[str, object] = {}
        for stmt in self.mod.tree.body:
            if isinstance(stmt, ast.FunctionDef):
                env[stmt.name] = _Func(stmt)
            elif isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                try:
                    env[stmt.targets[0].id] = ast.literal_eval(stmt.value)
                except (ValueError, SyntaxError):
                    pass
        return env

    # -- driver ----------------------------------------------------------

    def run(self, fn: ast.FunctionDef) -> None:
        env: dict[str, object] = {}
        for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs:
            env[a.arg] = _Ref(a.arg)
        if fn.args.vararg is not None:
            env[fn.args.vararg.arg] = _AbsTuple(fn.args.vararg.arg)
        try:
            self.exec_block(fn.body, env)
        except _Return:
            pass
        self._check_exit(fn)

    def _check_exit(self, fn: ast.FunctionDef) -> None:
        for key, count in self.ledger.items():
            if count > 0:
                nodes = self.ledger_nodes.get(key) or [fn]
                self.findings.append((
                    "dma-sem-balance", nodes[0],
                    f"{count} DMA signal(s) on {_key_str(key)} left "
                    f"outstanding at kernel exit — the copy outlives "
                    f"the kernel's scratch (wait every started DMA "
                    f"exactly once before returning)",
                ))

    # -- statements ------------------------------------------------------

    def _tick(self):
        self.steps += 1
        if self.steps > _STEP_CAP:
            raise _Abstain

    def exec_block(self, stmts, env) -> None:
        for stmt in stmts:
            self.exec_stmt(stmt, env)

    def exec_stmt(self, stmt, env) -> None:
        self._tick()
        if isinstance(stmt, ast.FunctionDef):
            cond = self._when_cond(stmt, env)
            # closures are LIVE references (Python semantics): an inner
            # def must see outer names bound after its definition — the
            # loop-bound model binding (range/fori on an opaque size)
            # depends on this
            if cond is _SKIP:
                env[stmt.name] = _Func(stmt, env)
            elif cond:
                # pl.when(True): the body runs inline, now
                self.call_func(_Func(stmt, env), [], {})
            return
        if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            self.exec_assign(stmt, env)
            return
        if isinstance(stmt, ast.Expr):
            self.eval(stmt.value, env)
            return
        if isinstance(stmt, ast.Return):
            raise _Return(self.eval(stmt.value, env)
                          if stmt.value is not None else None)
        if isinstance(stmt, ast.If):
            test = self.eval(stmt.test, env)
            branch = self._as_bool(test, stmt.test)
            self.exec_block(stmt.body if branch else stmt.orelse, env)
            return
        if isinstance(stmt, ast.For):
            self.exec_for(stmt, env)
            return
        if isinstance(stmt, ast.While):
            test = self.eval(stmt.test, env)
            if isinstance(test, _Opaque):
                if _block_has_dma(stmt.body):
                    raise _Abstain
                return
            # concrete while loops don't occur in kernel bodies here;
            # bound them defensively
            spins = 0
            while self._as_bool(test, stmt.test):
                self.exec_block(stmt.body, env)
                test = self.eval(stmt.test, env)
                spins += 1
                if spins > 64:
                    raise _Abstain
            return
        if isinstance(stmt, (ast.Pass, ast.Break, ast.Continue,
                             ast.Import, ast.ImportFrom, ast.Global,
                             ast.Nonlocal, ast.ClassDef)):
            return
        if isinstance(stmt, (ast.With, ast.Try)):
            if isinstance(stmt, ast.With):
                self.exec_block(stmt.body, env)
            else:
                self.exec_block(stmt.body, env)
                self.exec_block(stmt.finalbody, env)
            return
        if isinstance(stmt, (ast.Assert, ast.Delete, ast.Raise)):
            return
        # unknown statement kind: ignore (no DMA semantics)

    def _when_cond(self, fn: ast.FunctionDef, env):
        """``@pl.when(cond)`` decorator handling: _SKIP when the def is
        a plain function, else the (concrete) branch decision."""
        for dec in fn.decorator_list:
            if isinstance(dec, ast.Call) and (
                    self.mod.resolve(dec.func) or ""
            ).rsplit(".", 1)[-1] == "when" and dec.args:
                cond = self.eval(dec.args[0], env)
                return self._as_bool(cond, dec.args[0])
        return _SKIP

    def _as_bool(self, value, node) -> bool:
        if not isinstance(value, _Opaque):
            return bool(value)
        key = ast.dump(node)
        if key in self.memo:
            result = self.memo[key]
        else:
            # mode-switch predicates (``mode == "overlap"`` /
            # ``mode != "overlap_out"``) must stay mutually consistent
            # within one path: a factory kernel's branches on one
            # opaque subject would otherwise fork into impossible
            # combinations (two different equalities both true) and
            # fake ledger findings
            result = self._eq_family(node)
            if result is None:
                raise _NeedFork(key)
        self._note_eq(node, result)
        return result

    @staticmethod
    def _eq_parts(node) -> tuple[str, object, bool] | None:
        """(subject, constant, is_eq) of a single ``name ==/!= const``
        comparison, else None."""
        if not (isinstance(node, ast.Compare) and len(node.ops) == 1
                and isinstance(node.left, ast.Name)
                and isinstance(node.comparators[0], ast.Constant)):
            return None
        op = node.ops[0]
        if not isinstance(op, (ast.Eq, ast.NotEq)):
            return None
        return (node.left.id, node.comparators[0].value,
                isinstance(op, ast.Eq))

    def _eq_family(self, node) -> bool | None:
        parts = self._eq_parts(node)
        if parts is None:
            return None
        subject, const, is_eq = parts
        pinned, excluded = self._eq_state.get(subject, (None, set()))
        if pinned is not None:
            return (pinned == const) if is_eq else (pinned != const)
        if const in excluded:
            return False if is_eq else True
        return None

    def _note_eq(self, node, result: bool) -> None:
        parts = self._eq_parts(node)
        if parts is None:
            return
        subject, const, is_eq = parts
        pinned, excluded = self._eq_state.get(subject, (None, set()))
        if is_eq == result:        # == True or != False: pin
            pinned = const
        else:                      # == False or != True: exclude
            excluded = excluded | {const}
        self._eq_state[subject] = (pinned, excluded)

    def exec_for(self, stmt: ast.For, env) -> None:
        it = self.eval(stmt.iter, env)
        if isinstance(it, _Opaque):
            if _block_has_dma(stmt.body):
                raise _Abstain
            return
        if isinstance(it, range):
            items = list(it)
        elif isinstance(it, (list, tuple)):
            items = list(it)
        else:
            if _block_has_dma(stmt.body):
                raise _Abstain
            return
        for item in items:
            self._bind(stmt.target, item, env)
            self.exec_block(stmt.body, env)
        self.exec_block(stmt.orelse, env)

    def exec_assign(self, stmt, env) -> None:
        if isinstance(stmt, ast.AugAssign):
            value = _Opaque("aug")
            if isinstance(stmt.target, ast.Name):
                cur = env.get(stmt.target.id)
                rhs = self.eval(stmt.value, env)
                if isinstance(cur, int) and isinstance(rhs, int):
                    value = _arith(type(stmt.op), cur, rhs)
                elif isinstance(cur, list) and isinstance(
                        stmt.op, ast.Add) and isinstance(rhs, list):
                    value = cur + rhs
                env[stmt.target.id] = value
            elif isinstance(stmt.target, ast.Subscript):
                self.eval(stmt.value, env)
                self._store_subscript(stmt.target, _Opaque("aug"), env)
            return
        value = self.eval(stmt.value, env) if stmt.value is not None \
            else None
        targets = stmt.targets if isinstance(stmt, ast.Assign) \
            else [stmt.target]
        for tgt in targets:
            self._bind(tgt, value, env)

    def _bind(self, tgt, value, env) -> None:
        if isinstance(tgt, ast.Name):
            env[tgt.id] = value
            return
        if isinstance(tgt, (ast.Tuple, ast.List)):
            names = tgt.elts
            if isinstance(value, _AbsTuple):
                for e in names:
                    if isinstance(e, ast.Name):
                        env[e.id] = _Ref(e.id)
                return
            if isinstance(value, (list, tuple)) and len(value) == len(
                    names):
                for e, v in zip(names, value):
                    self._bind(e, v, env)
                return
            for e in names:
                if isinstance(e, ast.Name):
                    env[e.id] = _Opaque(e.id)
            return
        if isinstance(tgt, ast.Subscript):
            self._store_subscript(tgt, value, env)

    def _store_subscript(self, tgt: ast.Subscript, value, env) -> None:
        base = self.eval(tgt.value, env)
        if isinstance(base, list):
            idx = self.eval(tgt.slice, env)
            if isinstance(idx, int) and -len(base) <= idx < len(base):
                base[idx] = value
            return
        if isinstance(base, _Ref):
            idx = self._slot_index(tgt.slice, env)
            self._check_write((base.name, idx), tgt)

    def _slot_index(self, node, env):
        idx = self.eval(node, env)
        if isinstance(idx, int):
            return idx
        if isinstance(idx, (tuple, list)):
            # ref[i, ...]: a concrete LEADING element indexes the slot
            # axis; anything else (ref[:, ds(...)], ref[opaque, 0])
            # degrades to a whole-ref touch — conservative overlap,
            # never a guessed slot
            if idx and isinstance(idx[0], int):
                return idx[0]
            return "*"
        if isinstance(idx, _Opaque):
            return "?"
        return "*"

    # -- hazards ---------------------------------------------------------

    def _check_write(self, slot, node) -> None:
        """A local store (or a landing DMA, via start) into ``slot``:
        flag when an un-waited in-flight DMA still reads (send pending)
        or writes (recv pending) the same bytes."""
        for dma in self.inflight:
            if not dma.started:
                continue
            if not dma.send_waited and _overlaps(dma.src, slot):
                self.findings.append((
                    "dma-slot-reuse", node,
                    f"write to {_key_str(slot)} while the DMA started "
                    f"at line {dma.start_line()} is still reading it "
                    f"(send semaphore not waited) — the copy may send "
                    f"the NEW bytes",
                ))
            if not dma.recv_waited and _overlaps(dma.dst, slot):
                self.findings.append((
                    "dma-slot-reuse", node,
                    f"write to {_key_str(slot)} while the DMA started "
                    f"at line {dma.start_line()} is still landing "
                    f"there (recv semaphore not waited) — last writer "
                    f"is a race",
                ))

    def _check_read(self, slot, node) -> None:
        for dma in self.inflight:
            if dma.started and not dma.recv_waited and _overlaps(
                    dma.dst, slot):
                self.findings.append((
                    "dma-slot-reuse", node,
                    f"read of {_key_str(slot)} before the DMA started "
                    f"at line {dma.start_line()} has landed (recv "
                    f"semaphore not waited) — interpret mode "
                    f"serializes this; chips do not",
                ))

    # -- expressions -----------------------------------------------------

    def eval(self, node, env):
        self._tick()
        if node is None:
            return None
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in env:
                return env[node.id]
            if node.id in self.module_env:
                return self.module_env[node.id]
            if node.id in ("True", "False", "None"):  # pragma: no cover
                return {"True": True, "False": False, "None": None}[
                    node.id]
            return _Opaque(node.id)
        if isinstance(node, ast.Attribute):
            base = self.eval(node.value, env)
            if isinstance(base, _Ref) and node.attr == "at":
                return _AtProxy(base)
            if isinstance(base, (_DMA, list)):
                return _Method(base, node.attr)
            return _Opaque(node.attr)
        if isinstance(node, ast.Subscript):
            return self._load_subscript(node, env)
        if isinstance(node, ast.BinOp):
            left = self.eval(node.left, env)
            right = self.eval(node.right, env)
            if isinstance(left, (int, float)) and isinstance(
                    right, (int, float)):
                return _arith(type(node.op), left, right)
            if isinstance(left, list) and isinstance(right, list) \
                    and isinstance(node.op, ast.Add):
                return left + right
            if isinstance(left, list) and isinstance(right, int) \
                    and isinstance(node.op, ast.Mult):
                return left * right
            return _Opaque("binop")
        if isinstance(node, ast.UnaryOp):
            v = self.eval(node.operand, env)
            if isinstance(v, (int, float)) and isinstance(
                    node.op, ast.USub):
                return -v
            if isinstance(node.op, ast.Not) and not isinstance(
                    v, _Opaque):
                return not v
            return _Opaque("unary")
        if isinstance(node, ast.Compare):
            return self._compare(node, env)
        if isinstance(node, ast.BoolOp):
            vals = [self.eval(v, env) for v in node.values]
            if any(isinstance(v, _Opaque) for v in vals):
                return _Opaque("boolop")
            if isinstance(node.op, ast.And):
                return all(bool(v) for v in vals)
            return any(bool(v) for v in vals)
        if isinstance(node, ast.IfExp):
            test = self.eval(node.test, env)
            return self.eval(
                node.body if self._as_bool(test, node.test)
                else node.orelse, env)
        if isinstance(node, (ast.Tuple, ast.List)):
            return [self.eval(e, env) for e in node.elts]
        if isinstance(node, ast.Call):
            return self.eval_call(node, env)
        if isinstance(node, ast.Lambda):
            return _Opaque("lambda")
        if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
            return self._comprehension(node, env)
        if isinstance(node, ast.Slice):
            return slice(
                self.eval(node.lower, env) if node.lower else None,
                self.eval(node.upper, env) if node.upper else None,
                self.eval(node.step, env) if node.step else None,
            )
        if isinstance(node, ast.JoinedStr):
            return _Opaque("fstring")
        if isinstance(node, ast.Starred):
            return self.eval(node.value, env)
        return _Opaque(type(node).__name__)

    def _compare(self, node: ast.Compare, env):
        left = self.eval(node.left, env)
        result: object = True
        for op, comp in zip(node.ops, node.comparators):
            right = self.eval(comp, env)
            if isinstance(op, (ast.Is, ast.IsNot)):
                # the one judgement opaque values support: identity
                # against None (``if b_ref is not None`` unpacking)
                if left is None or right is None:
                    same = left is None and right is None
                    if isinstance(left, _Opaque) or isinstance(
                            right, _Opaque):
                        return _Opaque("is")
                    result = same if isinstance(op, ast.Is) else not same
                    left = right
                    continue
                if isinstance(left, _Opaque) or isinstance(
                        right, _Opaque):
                    return _Opaque("is")
                result = (left is right) if isinstance(op, ast.Is) \
                    else (left is not right)
                left = right
                continue
            if isinstance(left, _Opaque) or isinstance(right, _Opaque):
                return _Opaque("cmp")
            try:
                result = _COMPARES[type(op)](left, right)
            except (TypeError, KeyError):
                return _Opaque("cmp")
            if not result:
                return False
            left = right
        return result

    def _comprehension(self, node, env):
        if len(node.generators) != 1 or node.generators[0].ifs:
            return _Opaque("comp")
        gen = node.generators[0]
        it = self.eval(gen.iter, env)
        if not isinstance(it, (range, list, tuple)):
            return _Opaque("comp")
        out = []
        sub = dict(env)
        for item in it:
            self._bind(gen.target, item, sub)
            out.append(self.eval(node.elt, sub))
        return out

    def _load_subscript(self, node: ast.Subscript, env):
        base = self.eval(node.value, env)
        if isinstance(base, _AtProxy):
            idx = self._slot_index(node.slice, env)
            if idx == "?":
                return (base.ref.name, "?")
            return (base.ref.name, idx)
        if isinstance(base, _Ref):
            idx = self._slot_index(node.slice, env)
            if isinstance(idx, int):
                self._check_read((base.name, idx), node)
            return _Opaque(f"{base.name}[]")
        if isinstance(base, (list, tuple)):
            idx = self.eval(node.slice, env)
            if isinstance(idx, int):
                if -len(base) <= idx < len(base):
                    return base[idx]
                return _Opaque("index")
            if isinstance(idx, slice):
                try:
                    return list(base)[idx]
                except (TypeError, ValueError):
                    return _Opaque("slice")
            return _Opaque("index")
        if isinstance(base, _AbsTuple):
            idx = self.eval(node.slice, env)
            if isinstance(idx, slice):
                return _AbsTuple(base.prefix)
            if isinstance(idx, int):
                return _Ref(f"{base.prefix}[{idx}]")
            return _Opaque("abs-index")
        return _Opaque("subscript")

    # -- calls -----------------------------------------------------------

    def eval_call(self, node: ast.Call, env):
        # method dispatch on abstract objects first (DMA ops, lists)
        if isinstance(node.func, ast.Attribute):
            base = self.eval(node.func.value, env)
            if isinstance(base, _DMA):
                return self._dma_op(base, node.func.attr, node)
            if isinstance(base, list):
                return self._list_op(base, node.func.attr, node, env)
            if isinstance(base, _AtProxy):
                return _Opaque("at-method")
        func_val = None
        if isinstance(node.func, ast.Name):
            func_val = env.get(node.func.id,
                               self.module_env.get(node.func.id))
        if isinstance(func_val, _Method):
            # a bound DMA/list method stashed in a variable
            # (``w = d.wait_send; w()``) must dispatch, not dissolve
            # into an opaque call that silently drops the wait
            if isinstance(func_val.obj, _DMA):
                return self._dma_op(func_val.obj, func_val.attr, node)
            if isinstance(func_val.obj, list):
                return self._list_op(func_val.obj, func_val.attr, node,
                                     env)
            return _Opaque("method")
        if isinstance(func_val, _Func):
            args = [self.eval(a, env) for a in node.args]
            kwargs = {kw.arg: self.eval(kw.value, env)
                      for kw in node.keywords if kw.arg}
            return self.call_func(func_val, args, kwargs)
        if isinstance(func_val, _Partial):
            args = [self.eval(a, env) for a in node.args]
            kwargs = {kw.arg: self.eval(kw.value, env)
                      for kw in node.keywords if kw.arg}
            merged = list(func_val.args) + args
            mk = dict(func_val.kwargs)
            mk.update(kwargs)
            if isinstance(func_val.func, _Func):
                return self.call_func(func_val.func, merged, mk)
            return _Opaque("partial-call")
        if isinstance(func_val, _When):
            args = [self.eval(a, env) for a in node.args]
            if args and isinstance(args[0], _Func):
                if self._as_bool(func_val.cond, node):
                    return self.call_func(args[0], [], {})
            return None
        name = (self.mod.resolve(node.func) or "").rsplit(".", 1)[-1]
        return self._intrinsic(name, node, env)

    def call_func(self, fn: _Func, args, kwargs):
        fndef = fn.fndef
        env = dict(fn.closure)
        params = (fndef.args.posonlyargs + fndef.args.args)
        defaults = fndef.args.defaults
        # positional params, then defaults for the tail
        n_no_default = len(params) - len(defaults)
        for i, p in enumerate(params):
            if i < len(args):
                env[p.arg] = args[i]
            elif p.arg in kwargs:
                env[p.arg] = kwargs.pop(p.arg)
            elif i >= n_no_default:
                env[p.arg] = self.eval(defaults[i - n_no_default], env)
            else:
                env[p.arg] = _Opaque(p.arg)
        if fndef.args.vararg is not None:
            env[fndef.args.vararg.arg] = list(args[len(params):])
        kw_defaults = fndef.args.kw_defaults
        for i, p in enumerate(fndef.args.kwonlyargs):
            if p.arg in kwargs:
                env[p.arg] = kwargs.pop(p.arg)
            elif kw_defaults[i] is not None:
                env[p.arg] = self.eval(kw_defaults[i], env)
            else:
                env[p.arg] = _Opaque(p.arg)
        if len(self._stack) >= _DEPTH_CAP:
            raise _Abstain
        self._stack.append(fndef.name)
        try:
            self.exec_block(fndef.body, env)
            return None
        except _Return as r:
            return r.value
        finally:
            self._stack.pop()

    def _list_op(self, base: list, attr: str, node: ast.Call, env):
        args = [self.eval(a, env) for a in node.args]
        if attr == "append":
            base.append(args[0] if args else _Opaque("append"))
            return None
        if attr == "extend" and args and isinstance(args[0], list):
            base.extend(args[0])
            return None
        if attr == "pop":
            if base:
                return base.pop(args[0] if args and isinstance(
                    args[0], int) else -1)
            return _Opaque("pop")
        return _Opaque(f"list.{attr}")

    # -- DMA semantics ---------------------------------------------------

    def _sem_key(self, value, node) -> tuple[str, object]:
        slot = _slot_of(value)
        if slot is None:
            raise _Abstain
        if slot[1] == "?":
            raise _Abstain
        return slot

    def _build_dma(self, node: ast.Call, env, remote: bool):
        args = [self.eval(a, env) for a in node.args]
        kwargs = {kw.arg: self.eval(kw.value, env)
                  for kw in node.keywords if kw.arg}
        if remote:
            src = kwargs.get("src_ref", args[0] if len(args) > 0 else None)
            dst = kwargs.get("dst_ref", args[1] if len(args) > 1 else None)
            send = kwargs.get("send_sem",
                              args[2] if len(args) > 2 else None)
            recv = kwargs.get("recv_sem",
                              args[3] if len(args) > 3 else None)
            send_key = self._sem_key(send, node)
            recv_key = self._sem_key(recv, node)
        else:
            src = kwargs.get("src_ref", args[0] if len(args) > 0 else None)
            dst = kwargs.get("dst_ref", args[1] if len(args) > 1 else None)
            sem = kwargs.get("sem", args[2] if len(args) > 2 else None)
            send_key = None
            recv_key = self._sem_key(sem, node)
        return _DMA(_slot_of(src), _slot_of(dst), send_key, recv_key,
                    remote, node)

    def _signal(self, key, node) -> None:
        self.ledger[key] = self.ledger.get(key, 0) + 1
        self.ledger_nodes.setdefault(key, []).append(node)

    def _consume(self, key, node, what: str) -> bool:
        if self.ledger.get(key, 0) <= 0:
            self.findings.append((
                "dma-sem-balance", node,
                f"{what} on {_key_str(key)} with no outstanding signal "
                f"— an earlier wait already consumed it (the PR 8 "
                f"drain double-wait) or the matching start is missing; "
                f"on chip this wait never returns",
            ))
            return False
        self.ledger[key] -= 1
        nodes = self.ledger_nodes.get(key)
        if nodes:
            nodes.pop(0)
        return True

    def _dma_op(self, dma: _DMA, attr: str, node: ast.Call):
        if attr == "start":
            dma.started = True
            dma.start_node = node
            if dma.send_key is not None:
                self._signal(dma.send_key, node)
            if dma.recv_key is not None:
                self._signal(dma.recv_key, node)
            if dma.dst is not None:
                self._check_write(dma.dst, node)
                self._track_recv_family(dma, node)
            if dma.src is not None:
                self._check_read(dma.src, node)
            self.inflight.append(dma)
            return None
        if attr in ("wait", "wait_send", "wait_recv"):
            if attr in ("wait", "wait_send") and dma.send_key is not None:
                if self._consume(dma.send_key, node, f".{attr}()"):
                    self._mark_waited(dma.send_key, "send")
            if attr in ("wait", "wait_recv") and dma.recv_key is not None:
                if self._consume(dma.recv_key, node, f".{attr}()"):
                    self._mark_waited(dma.recv_key, "recv")
            return None
        return _Opaque(f"dma.{attr}")

    def _mark_waited(self, key, channel: str) -> None:
        """The oldest in-flight DMA on this semaphore channel landed."""
        for dma in self.inflight:
            if channel == "send" and dma.send_key == key \
                    and not dma.send_waited:
                dma.send_waited = True
                return
            if channel == "recv" and dma.recv_key == key \
                    and not dma.recv_waited:
                dma.recv_waited = True
                if dma.send_key is None:
                    # a local copy has ONE semaphore: its wait means
                    # the whole transfer (read side included) is done
                    dma.send_waited = True
                return

    def _track_recv_family(self, dma: _DMA, node) -> None:
        if dma.dst is None or dma.recv_key is None or not dma.remote:
            return
        buf = dma.dst[0]
        fams = self.recv_sems_by_buf.setdefault(buf, {})
        sem_name = dma.recv_key[0]
        if sem_name not in fams:
            if fams:
                other = next(iter(fams))
                self.findings.append((
                    "dma-slot-reuse", node,
                    f"scratch {buf!r} receives DMAs under two "
                    f"semaphore families ({other!r}, {sem_name!r}) — "
                    f"phase-crossed recv slots (the PR 8 gather-into-"
                    f"reduce-scatter-slot bug); give each phase a "
                    f"dedicated recv buffer",
                ))
            fams[sem_name] = node

    # -- intrinsics ------------------------------------------------------

    def _intrinsic(self, name: str, node: ast.Call, env):
        if name in _DMA_BUILDERS:
            return self._build_dma(
                node, env, remote=(name == "make_async_remote_copy"))
        if name == "when":
            cond = self.eval(node.args[0], env) if node.args else True
            return _When(cond)
        if name == "run_scoped":
            return self._run_scoped(node, env)
        if name == "fori_loop":
            return self._fori(node, env)
        if name == "partial":
            args = [self.eval(a, env) for a in node.args]
            kwargs = {kw.arg: self.eval(kw.value, env)
                      for kw in node.keywords if kw.arg}
            if args and isinstance(args[0], _Func):
                return _Partial(args[0], args[1:], kwargs)
            return _Opaque("partial")
        if name == "range":
            return self._range(node, env)
        if name == "rem":
            args = [self.eval(a, env) for a in node.args]
            if len(args) == 2 and all(
                    isinstance(a, int) for a in args) and args[1] != 0:
                return args[0] % args[1]
            return _Opaque("rem")
        if name == "len":
            args = [self.eval(a, env) for a in node.args]
            if args and isinstance(args[0], (list, tuple)):
                return len(args[0])
            return _Opaque("len")
        if name in ("min", "max", "abs", "int"):
            args = [self.eval(a, env) for a in node.args]
            if args and all(isinstance(a, (int, float)) for a in args):
                return {"min": min, "max": max, "abs": abs,
                        "int": int}[name](*args)
            return _Opaque(name)
        # anything else (jnp ops, pl.ds, program_id, axis_index …):
        # evaluate args for their ref-read side conditions, result is
        # opaque data
        for a in node.args:
            self.eval(a, env)
        for kw in node.keywords:
            self.eval(kw.value, env)
        return _Opaque(name)

    def _range(self, node: ast.Call, env):
        vals = []
        for i, a in enumerate(node.args):
            v = self.eval(a, env)
            if isinstance(v, _Opaque):
                # the ring-size model: an opaque bound (the runtime
                # mesh axis size) unrolls at MODEL_RING devices; a
                # plain-Name bound is also BOUND to the model so
                # ``s < size - 1`` inside the loop resolves
                # consistently
                v = MODEL_RING
                if isinstance(a, ast.Name):
                    env[a.id] = MODEL_RING
            if not isinstance(v, int):
                return _Opaque("range")
            vals.append(v)
        try:
            return range(*vals)
        except (TypeError, ValueError):
            return _Opaque("range")

    def _run_scoped(self, node: ast.Call, env):
        body = self.eval(node.args[0], env) if node.args else None
        if not isinstance(body, _Func):
            if _block_has_dma([node]):
                raise _Abstain
            return _Opaque("run_scoped")
        # allocations bind to the body's params: keywords by name, any
        # positional extras by position (both API forms are legal)
        params = body.fndef.args.posonlyargs + body.fndef.args.args
        args = [_Ref(p.arg) for p in params[:len(node.args) - 1]]
        kwargs = {kw.arg: _Ref(kw.arg) for kw in node.keywords if kw.arg}
        return self.call_func(body, args, kwargs)

    def _fori(self, node: ast.Call, env):
        if len(node.args) < 4:
            return _Opaque("fori")
        lo = self.eval(node.args[0], env)
        hi = self.eval(node.args[1], env)
        body = self.eval(node.args[2], env)
        carry = self.eval(node.args[3], env)
        if isinstance(lo, _Opaque):
            lo = 0
        if isinstance(hi, _Opaque):
            hi = MODEL_RING
            if isinstance(node.args[1], ast.Name):
                env[node.args[1].id] = MODEL_RING
        if not (isinstance(lo, int) and isinstance(hi, int)
                and isinstance(body, _Func)):
            if _block_has_dma([node]):
                raise _Abstain
            return _Opaque("fori")
        for i in range(lo, min(hi, lo + 64)):
            carry = self.call_func(body, [i, carry], {})
        return carry


_SKIP = object()

_COMPARES = {
    ast.Eq: lambda a, b: a == b,
    ast.NotEq: lambda a, b: a != b,
    ast.Lt: lambda a, b: a < b,
    ast.LtE: lambda a, b: a <= b,
    ast.Gt: lambda a, b: a > b,
    ast.GtE: lambda a, b: a >= b,
    ast.In: lambda a, b: a in b,
    ast.NotIn: lambda a, b: a not in b,
}


def _arith(op, a, b):
    try:
        if op is ast.Add:
            return a + b
        if op is ast.Sub:
            return a - b
        if op is ast.Mult:
            return a * b
        if op is ast.FloorDiv:
            return a // b
        if op is ast.Mod:
            return a % b
        if op is ast.Div:
            return a / b
        if op is ast.Pow:
            return a ** b
        if op is ast.BitXor:
            return a ^ b
    except (ZeroDivisionError, TypeError, OverflowError):
        pass
    return _Opaque("arith")


def _key_str(key: tuple[str, object]) -> str:
    name, idx = key
    if idx == "*":
        return name
    return f"{name}[{idx}]"


def _block_has_dma(stmts) -> bool:
    """Whether a statement/expression list contains DMA-relevant calls
    — the abstain trigger for loops the interpreter cannot unroll."""
    for stmt in stmts:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Attribute) and node.attr in (
                    _DMA_WAITS | {"start"} | _DMA_BUILDERS):
                return True
            if isinstance(node, ast.Name) and node.id in _DMA_BUILDERS:
                return True
    return False


# ---------------------------------------------------------------------------
# driver: forked runs per kernel, cached per module
# ---------------------------------------------------------------------------


_LEDGER_CACHE: dict[tuple[str, int], list[tuple[str, ast.AST, str]]] = {}


def ledger_findings(mod: ModuleInfo) -> list[tuple[str, ast.AST, str]]:
    """All ledger/slot findings for one module: every kernel body, every
    branch-memo path, deduped. A kernel that abstains contributes
    nothing (conservative — silence is never a guess)."""
    cache_key = (mod.path, hash(mod.source))
    if cache_key in _LEDGER_CACHE:
        return _LEDGER_CACHE[cache_key]
    out: list[tuple[str, ast.AST, str]] = []
    for fn in _kernel_roots(mod):
        out.extend(_analyze_kernel(mod, fn))
    _LEDGER_CACHE[cache_key] = out
    if len(_LEDGER_CACHE) > 256:
        _LEDGER_CACHE.pop(next(iter(_LEDGER_CACHE)))
    return out


def _analyze_kernel(mod: ModuleInfo,
                    fn: ast.FunctionDef) -> list[tuple[str, ast.AST, str]]:
    pending: list[dict[str, bool]] = [{}]
    done = 0
    findings: list[tuple[str, ast.AST, str]] = []
    seen: set[tuple[str, int, str]] = set()
    while pending:
        memo = pending.pop()
        run = _KernelRun(mod, memo)
        run._stack = []
        try:
            run.run(fn)
        except _NeedFork as f:
            if done + len(pending) >= _PATH_CAP:
                return []  # fork explosion: abstain
            pending.append({**memo, f.key: True})
            pending.append({**memo, f.key: False})
            continue
        except _Abstain:
            return []
        except RecursionError:  # pragma: no cover - defensive
            return []
        done += 1
        if done > _PATH_CAP:
            return []
        for kind, node, msg in run.findings:
            key = (kind, getattr(node, "lineno", 0), msg)
            if key not in seen:
                seen.add(key)
                findings.append((kind, node, msg))
    return findings


# ---------------------------------------------------------------------------
# the rules
# ---------------------------------------------------------------------------


@register
class DmaSemBalanceRule(Rule):
    """The PR 8 drain bug class, statically: the semaphore ledger must
    balance — every wait consumes exactly one outstanding signal, and
    no signal outlives the kernel. A wait with nothing outstanding is
    a deadlock on chip (one signal per DMA; a slot-reuse wait may have
    consumed it steps earlier); a signal left at exit is a DMA racing
    the kernel's scratch teardown."""

    name = "dma-sem-balance"
    family = "pallaslint"
    summary = ("kernel DMA semaphore ledger imbalance: double-wait, "
               "wait-without-signal, or signals outstanding at exit")
    hint = ("wait every started DMA exactly once per channel; after a "
            "slot-reuse wait chain, drain ONLY the still-outstanding "
            "tail (comm/fused.py's dmas[-1].wait_send() pattern)")

    def check(self, mod: ModuleInfo, config: AnalysisConfig
              ) -> Iterable[Finding]:
        for kind, node, msg in ledger_findings(mod):
            if kind == self.name:
                yield self.finding(mod, node, msg)


@register
class DmaSlotReuseRule(Rule):
    """Dedicated-slot discipline, checkable: no write may land in a
    slot an un-waited DMA still reads or writes, no read may consume a
    slot whose DMA has not landed, and no scratch buffer may serve as
    the recv target of two DMA phases (the PR 8 gather-into-
    ``rs_recv`` bug — nothing orders one phase's completion after the
    other's remote consumption)."""

    name = "dma-slot-reuse"
    family = "pallaslint"
    summary = ("scratch slot reused while a DMA is in flight, or one "
               "recv buffer shared across DMA phases")
    hint = ("wait the in-flight DMA's semaphore before touching its "
            "slot, and give each ring phase its own recv scratch "
            "(comm/fused.py's rs_recv/ag_recv split)")

    def check(self, mod: ModuleInfo, config: AnalysisConfig
              ) -> Iterable[Finding]:
        for kind, node, msg in ledger_findings(mod):
            if kind == self.name:
                yield self.finding(mod, node, msg)


@register
class CollectiveIdCollisionRule(Rule):
    """Same-id collective kernels share barrier/DMA state on chip: two
    concurrent kernels with one ``collective_id`` hang or corrupt, and
    interpret mode never notices. The ``ops.tiling.collective_id``
    registry assigns ids by name (collisions impossible by
    construction); this rule flags hand-picked integers and any two
    call sites sharing an id or a registry name in one module."""

    name = "collective-id-collision"
    family = "pallaslint"
    summary = ("hand-picked or colliding collective_id (use the "
               "ops.tiling.collective_id registry)")
    hint = ("pass collective_id=tiling.collective_id('<unique.name>') "
            "— the registry makes two concurrent kernels sharing an "
            "id impossible by construction")

    # duplicate detection is PER MODULE (the engine's deliberate
    # scope, rules.py module docstring); the cross-module half of the
    # invariant — no two call sites anywhere registering one name —
    # is test-pinned over the whole package in tests/test_analysis.py

    def check(self, mod: ModuleInfo, config: AnalysisConfig
              ) -> Iterable[Finding]:
        seen: dict[object, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg != "collective_id":
                    continue
                key = self._id_key(mod, kw.value)
                if key is None:
                    continue
                kind, value = key
                if kind == "literal":
                    yield self.finding(
                        mod, kw.value,
                        f"hand-picked collective_id={value}: ids by "
                        f"convention collide silently — register a "
                        f"name with ops.tiling.collective_id instead",
                    )
                if key in seen:
                    yield self.finding(
                        mod, kw.value,
                        f"collective_id {value!r} already used at "
                        f"line {seen[key].lineno} in this module — "
                        f"concurrent same-id kernels share barrier "
                        f"state (the PR 8 shared-id bug)",
                    )
                else:
                    seen[key] = kw.value

    @staticmethod
    def _id_key(mod: ModuleInfo, value: ast.AST):
        if isinstance(value, ast.Constant) and isinstance(
                value.value, int):
            return ("literal", value.value)
        if isinstance(value, ast.Call):
            base = (mod.resolve(value.func) or "").rsplit(".", 1)[-1]
            if base == "collective_id" and value.args and isinstance(
                    value.args[0], ast.Constant):
                return ("registry", value.args[0].value)
        return None


@register
class KernelDtypeCastRule(Rule):
    """The PR 8 dtype-discipline hole: a matmul widened with
    ``preferred_element_type=`` stored straight into a kernel ref.
    Interpret mode inserts the implicit narrowing cast; Mosaic's
    lowering need not agree (and a silent f32 landing in a bf16 ref is
    a parity break either way). The discipline —
    ``.astype(o_ref.dtype)`` on every widened store — is what the
    fused/flash kernels already do; this makes it checked."""

    name = "kernel-dtype-cast"
    family = "pallaslint"
    summary = ("widened matmul stored into a kernel ref without "
               ".astype(ref.dtype)")
    hint = ("end the store with .astype(<ref>.dtype) — the explicit "
            "cast is the contract interpret and Mosaic both honor")

    _WIDENING = frozenset({"dot", "dot_general", "einsum"})

    def check(self, mod: ModuleInfo, config: AnalysisConfig
              ) -> Iterable[Finding]:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Assign)
                    and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Subscript)
                    and isinstance(node.targets[0].value, ast.Name)
                    and isinstance(node.value, ast.Call)):
                continue
            call = node.value
            base = (mod.resolve(call.func) or "").rsplit(".", 1)[-1]
            if base not in self._WIDENING:
                continue
            if not any(kw.arg == "preferred_element_type"
                       for kw in call.keywords):
                continue
            ref = node.targets[0].value.id
            yield self.finding(
                mod, node,
                f"widened {base} (preferred_element_type=...) stored "
                f"into {ref!r} without .astype({ref}.dtype) — "
                f"interpret mode forgives the implicit cast, Mosaic "
                f"need not",
            )


@register
class VmemBudgetRule(Rule):
    """A kernel whose VMEM working set exceeds its
    ``vmem_limit_bytes`` (or Mosaic's 16 MB default scoped limit when
    none is set) fails at lowering on chip — after the queue wait, on
    budgeted chip time. The estimator
    (``analysis/vmem.py``) sums BlockSpec blocks + scratch shapes;
    this rule fires only on totals resolvable from literals alone
    (symbolic shapes are ``--vmem-report``'s model-dimension
    territory, reported, never flagged)."""

    name = "vmem-budget"
    family = "pallaslint"
    summary = ("literal-resolvable kernel VMEM footprint exceeds its "
               "vmem_limit_bytes")
    hint = ("shrink the block/scratch shapes, stream the grid, or "
            "raise vmem_limit_bytes deliberately (and justify it — "
            "the physical budget is ~16 MB/core on most parts)")

    def check(self, mod: ModuleInfo, config: AnalysisConfig
              ) -> Iterable[Finding]:
        for est in vmem_mod.estimate_module(mod):
            if est.exact_bytes is None:
                continue
            if est.exact_bytes > est.limit_bytes:
                yield self.finding(
                    mod, est.node,
                    f"kernel {est.kernel!r} needs at least "
                    f"{est.exact_bytes:,} bytes of VMEM (the "
                    f"literal-resolvable blocks+scratch alone) "
                    f"against a {est.limit_bytes:,}-byte limit"
                    + (" (Mosaic default)" if est.limit_default
                       else ""),
                )
