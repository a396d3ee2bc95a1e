"""Argus pass ``async``: hazards in the coroutine fabric.

The actor fabric runs ~139 coroutines over one event loop; a single
blocking call in any of them stalls every replica, gossip follower and
HTTP handler in the process. The rules:

- ``blocking-call`` — a known-blocking callable invoked directly inside
  an ``async def``: ``time.sleep``, ``subprocess.*``, synchronous file
  I/O (``open`` / pathlib ``read_text``-family), ``.result()`` on a
  future, ``block_until_ready``, the native bignum entry points
  (``powmod`` / ``powmod_batch`` / ``fold`` / ``modmul_fold*`` release
  the GIL but still block the calling thread for the whole modexp), and
  ``flight.record`` (a ``threading.Lock`` plus a synchronous disk write
  on the fault path — use ``flight.record_async``). Passing one of these
  as an argument (``asyncio.to_thread(fold, ...)``) is the sanctioned
  form and is not flagged.
- ``unawaited-coroutine`` — a bare expression statement calling a
  module-level ``async def`` by name, or ``self.X()`` where ``X`` is an
  async method of the enclosing class: the coroutine object is created
  and dropped, the body never runs. (Deliberately narrow — resolving
  arbitrary attribute chains cross-class is beyond an intra-procedural
  pass, and a near-miss here is worse than a miss.)
- ``dropped-task`` — ``ensure_future``/``create_task`` as a bare
  expression statement: no handle retained, so the task can be GC'd
  mid-flight and its exception is never observed.
- ``bare-task-spawn`` — any direct ``asyncio.ensure_future`` call under
  ``dds_tpu/``: the repo discipline is ``utils.tasks.supervised_task``,
  which retains the handle and logs + flight-records unexpected crashes
  (a bare spawn dies silently — the ``_key_sync_loop`` class of bug).
- ``lock-across-await`` — a synchronous ``with <lock>`` in a coroutine
  whose body awaits: every other coroutine contending for that
  ``threading.Lock`` blocks the loop until the awaited op completes.
"""

from __future__ import annotations

import ast

from tools.argus.engine import (
    Finding,
    dotted_name,
    iter_scopes,
    scope_calls,
    walked_stmts,
)

# dotted suffixes of callables that block the event loop (matched against
# the END of the call's dotted name, so `time.sleep` catches
# `time.sleep(...)` however `time` is bound)
BLOCKING_SUFFIXES = {
    "time.sleep": "blocks the loop; use asyncio.sleep",
    "subprocess.run": "blocks the loop; use asyncio.create_subprocess_exec",
    "subprocess.call": "blocks the loop; use asyncio.create_subprocess_exec",
    "subprocess.check_call": "blocks the loop; use asyncio.create_subprocess_exec",
    "subprocess.check_output": "blocks the loop; use asyncio.create_subprocess_exec",
    "os.system": "blocks the loop; use asyncio.create_subprocess_exec",
    "os.fsync": "sync disk flush (the fsync-before-rename discipline is "
                "worker-thread work); use asyncio.to_thread",
    "os.fdatasync": "sync disk flush (the fsync-before-rename discipline "
                    "is worker-thread work); use asyncio.to_thread",
    "flight.record": "threading.Lock + sync disk write on the fault path; "
                     "use flight.record_async",
}

# bare attribute names that block regardless of the owner expression
BLOCKING_ATTRS = {
    "block_until_ready": "host-side device sync; only obs/kprof.profiled "
                         "may block (run via asyncio.to_thread)",
    "read_text": "sync file I/O; use asyncio.to_thread",
    "write_text": "sync file I/O; use asyncio.to_thread",
    "read_bytes": "sync file I/O; use asyncio.to_thread",
    "write_bytes": "sync file I/O; use asyncio.to_thread",
    "result": "blocks until the future resolves; await it instead",
}

# native/batched bignum entries: GIL-releasing but thread-blocking for a
# full modexp — run them via asyncio.to_thread like server._fold does
BLOCKING_COMPUTE = {"powmod", "powmod_batch", "fold", "modmul_fold"}

SPAWNERS = {"ensure_future", "create_task"}


def _is_lockish(expr: ast.expr) -> bool:
    name = dotted_name(expr)
    last = name.rsplit(".", 1)[-1].lower()
    return "lock" in last


class AsyncHazardPass:
    pass_id = "async"

    def applies(self, rel_path: str) -> bool:
        return rel_path.endswith(".py")

    # `bare-task-spawn` is repo discipline, not a universal hazard: only
    # dds_tpu/ is held to supervised_task (benchmarks/tests spawn freely)
    def _spawn_rule_applies(self, rel_path: str) -> bool:
        return (rel_path.startswith("dds_tpu/") or "/dds_tpu/" in rel_path
                or "fixtures/argus" in rel_path)

    def run(self, tree: ast.Module, src: str, rel_path: str) -> list[Finding]:
        out: list[Finding] = []
        module_async = {
            s.name for s in tree.body if isinstance(s, ast.AsyncFunctionDef)
        }
        class_async = self._class_async_methods(tree)
        for scope in iter_scopes(tree):
            if scope.is_async:
                out += self._blocking_calls(scope, rel_path)
                out += self._locks_across_await(scope, rel_path)
            out += self._task_rules(scope, rel_path, module_async,
                                    class_async)
        return out

    # ------------------------------------------------------------- helpers

    @staticmethod
    def _class_async_methods(tree: ast.Module) -> dict[str, set[str]]:
        """Dotted class name -> names of its async methods, for resolving
        ``self.X()`` inside a method of that class."""
        out: dict[str, set[str]] = {}

        def walk(body, prefix):
            for stmt in body:
                if isinstance(stmt, ast.ClassDef):
                    cname = f"{prefix}{stmt.name}"
                    out[cname] = {
                        s.name for s in stmt.body
                        if isinstance(s, ast.AsyncFunctionDef)
                    }
                    walk(stmt.body, cname + ".")
                elif isinstance(stmt, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    walk(stmt.body, f"{prefix}{stmt.name}.")

        walk(tree.body, "")
        return out

    def _blocking_calls(self, scope, rel_path: str) -> list[Finding]:
        out = []
        for call in scope_calls(scope.body):
            name = dotted_name(call.func)
            last = name.rsplit(".", 1)[-1]
            why = None
            for suffix, reason in BLOCKING_SUFFIXES.items():
                if name == suffix or name.endswith("." + suffix):
                    why = reason
                    break
            if why is None and isinstance(call.func, ast.Attribute):
                if last in BLOCKING_ATTRS:
                    why = BLOCKING_ATTRS[last]
            if why is None and last in BLOCKING_COMPUTE and name != "?":
                why = ("native bignum compute blocks the calling thread; "
                       "run via asyncio.to_thread")
            if why is None and isinstance(call.func, ast.Name) \
                    and call.func.id == "open":
                why = "sync file I/O; use asyncio.to_thread"
            if why is not None:
                out.append(Finding(
                    rel_path, call.lineno, self.pass_id, "blocking-call",
                    f"blocking call {name}() inside async def "
                    f"{scope.name} — {why}",
                    symbol=name, scope=scope.name,
                ))
        return out

    def _locks_across_await(self, scope, rel_path: str) -> list[Finding]:
        out = []
        for stmt in walked_stmts(scope.body):
            node = stmt
            if not isinstance(node, ast.With):
                continue
            if not any(_is_lockish(i.context_expr) for i in node.items):
                continue
            if any(isinstance(n, (ast.Await, ast.AsyncFor, ast.AsyncWith))
                   for stmt in node.body for n in ast.walk(stmt)):
                lock = next(dotted_name(i.context_expr) for i in node.items
                            if _is_lockish(i.context_expr))
                out.append(Finding(
                    rel_path, node.lineno, self.pass_id, "lock-across-await",
                    f"threading lock {lock} held across await in "
                    f"{scope.name} — every contending coroutine blocks the "
                    f"loop; use asyncio.Lock or release before awaiting",
                    symbol=lock, scope=scope.name,
                ))
        return out

    def _task_rules(self, scope, rel_path: str, module_async: set[str],
                    class_async: dict[str, set[str]]) -> list[Finding]:
        out = []
        spawn_rule = self._spawn_rule_applies(rel_path)
        # async methods of the class enclosing this scope, if any
        own_class = scope.name.rsplit(".", 1)[0] if "." in scope.name else ""
        own_async = class_async.get(own_class, set())
        for stmt in walked_stmts(scope.body):
            if not isinstance(stmt, ast.Expr) or not isinstance(
                    stmt.value, ast.Call):
                continue
            call = stmt.value
            name = dotted_name(call.func)
            last = name.rsplit(".", 1)[-1]
            unawaited = (
                (isinstance(call.func, ast.Name) and last in module_async)
                or (isinstance(call.func, ast.Attribute)
                    and isinstance(call.func.value, ast.Name)
                    and call.func.value.id == "self"
                    and last in own_async)
            )
            if last in SPAWNERS:
                out.append(Finding(
                    rel_path, call.lineno, self.pass_id, "dropped-task",
                    f"{name}() handle dropped in {scope.name} — the task "
                    f"can be GC'd mid-flight and its exception is never "
                    f"observed; use utils.tasks.supervised_task",
                    symbol=name, scope=scope.name,
                ))
            elif unawaited:
                out.append(Finding(
                    rel_path, call.lineno, self.pass_id,
                    "unawaited-coroutine",
                    f"coroutine {name}() called but never awaited in "
                    f"{scope.name} — the body never runs",
                    symbol=name, scope=scope.name,
                ))
        if spawn_rule:
            for call in scope_calls(scope.body):
                name = dotted_name(call.func)
                if name == "asyncio.ensure_future" or \
                        name.endswith(".asyncio.ensure_future"):
                    out.append(Finding(
                        rel_path, call.lineno, self.pass_id,
                        "bare-task-spawn",
                        f"direct asyncio.ensure_future in {scope.name} — "
                        f"use utils.tasks.supervised_task so the handle is "
                        f"retained and crashes are logged + flight-recorded",
                        symbol=name, scope=scope.name,
                    ))
        return out
