"""The five dabtlint checkers over the project event model.

Interprocedural core: per-function summaries computed to a fixpoint over the
project call graph —

- ``acquires*(f)``  every lock class ``f`` may acquire, directly or through
  any resolvable call chain
- ``resolves*(f)``  whether ``f`` may resolve a Future (set_result /
  set_exception / cancel / a helper like ``_safe_resolve``), and via whom

DABT101 builds the global lock-acquisition-order graph from three edge
sources: direct nested acquisition, calls made while holding a lock (edges to
everything the callee may acquire), and Future-resolution sites while holding
a lock (edges to everything any registered done-callback may acquire — the
exact shape of both PR 7 deadlocks, where ``Future.set_result`` under lock A
ran a router callback that took lock B).  A cycle in that graph is a
deadlock two threads can reach by interleaving.
"""

from __future__ import annotations

import ast
import fnmatch
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .findings import Finding
from .locks import FunctionEvents, _acquire_is_timed, _expr_display, extract_events
from .project import FunctionInfo, Project

# Functions whose call trees are decode-tick hot paths: a device->host sync
# anywhere under these stalls the pipelined tick (DABT104).  Matched with
# fnmatch against both the bare qualname and "module.py::qualname".
HOT_PATH_PATTERNS: Tuple[str, ...] = (
    "*._process_tick",
    "_process_tick",
    "*._issue_tick",
    "decode_step*",
    "*.decode_step*",
    "*spec_tick*",
    "verify_tree_step*",
    "commit_tree_path*",
    "*paged_gqa_decode_attention",
    "paged_tree_attention",
    "insert_sequences_paged",
    "prefill_suffix_paged",
    "prefill_chunk_paged",
    # fused multi-step decode tick (decode_steps > 1): the N-step scan body
    # and its builder — a host sync inside would stall ALL N steps of every
    # tick, so the builder closure tree is a root in its own right
    "*._make_decode_tick*",
    # double-buffered host->device uploads: runs between ticks while device
    # work is in flight; a sync here would serialize the overlap away
    "*._upload_dirty",
    "*._prestage_uploads",
    "*._refresh_sampling",
    # quantized in-dot dequant (int8 per-channel / int4 grouped): the weight
    # read path of every decode/prefill/verify dot
    "qeinsum",
    "*.qeinsum",
    "unpack_int4",
    # observability recorder entry points (serving/obs.py): called from the
    # tick path's host bookkeeping, so metric recording can never silently
    # add a device sync — roots in their own right, independent of whether
    # the engine's `self.obs` attribute type resolves
    "*EngineObs.on_tick",
    "*EngineObs.on_spec_tick",
    "*EngineObs.on_first_token",
    "*EngineObs.on_token_gap",
    "*Histogram.observe",
    "*FlightRecorder.record",
    # engine-loop time ledger (serving/obs.py LoopLedger): span enter/exit
    # run ~10 times per loop iteration around every dispatch and the tick's
    # result wait; its device-queue half stamps every dispatch
    # (note_dispatch, which also feeds the padding counters) and closes a
    # segment where a result's wait ended (note_marker).  The server's
    # per-delta stream-lag stamp sits between a token and its write
    "*_PhaseSpan.__enter__",
    "*_PhaseSpan.__exit__",
    "*LoopLedger.span",
    "*LoopLedger.seconds",
    "*LoopLedger.note_dispatch",
    "*LoopLedger.note_marker",
    "*_StreamLag.note",
)

# Modules under these path segments are clock-disciplined candidates for
# DABT105 (the serving plane's injectable-clock convention).
CLOCK_DISCIPLINE_DIRS: Tuple[str, ...] = ("serving",)

HOST_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
# canonical module.attr forms; call sites are canonicalized through the
# module's import table first, so `import numpy as _np; _np.asarray(x)`
# resolves to numpy.asarray and cannot dodge the checker via an alias
HOST_SYNC_CALLS = {
    "numpy.asarray",
    "numpy.array",
    "jax.device_get",
}
BLOCKING_HTTP_PREFIXES = ("requests.", "urllib.request.", "http.client.")
RAW_TIME_CALLS = {"time.time", "time.monotonic", "time.sleep"}


def _dedupe(findings: Iterable[Finding]) -> List[Finding]:
    # collapse per (identity, line) — identical keys at DIFFERENT lines stay
    # separate findings (each site can be suppressed on its own line; one
    # baseline entry still accepts all of them, by design)
    seen: Dict[Tuple, Finding] = {}
    for f in findings:
        seen.setdefault((f.key, f.line), f)
    return sorted(seen.values(), key=lambda f: (f.module, f.line, f.code, f.detail))


def _short_lock(lock: str) -> str:
    """'pkg/serving/scheduler.py::RequestScheduler._lock' ->
    'RequestScheduler._lock' (display/detail form: file-move stable)."""
    return lock.rsplit("::", 1)[-1]


class Analysis:
    def __init__(self, project: Project):
        self.project = project
        self.events: Dict[str, FunctionEvents] = extract_events(project)
        self._by_fi: Dict[int, FunctionEvents] = {
            id(ev.fi): ev for ev in self.events.values()
        }
        self.acquires_trans: Dict[str, Set[str]] = {}
        self.resolves_trans: Dict[str, Optional[str]] = {}
        self.callbacks: List[FunctionInfo] = []
        self._summarize()

    # ------------------------------------------------------------- summaries
    def _summarize(self) -> None:
        acq: Dict[str, Set[str]] = {}
        res: Dict[str, Optional[str]] = {}
        for disp, ev in self.events.items():
            acq[disp] = {a.lock for a in ev.acquires}
            res[disp] = "directly" if ev.resolves else None
        changed = True
        while changed:
            changed = False
            for disp, ev in self.events.items():
                for call in ev.calls:
                    for g in call.targets:
                        gdisp = g.display
                        extra = acq.get(gdisp, set()) - acq[disp]
                        if extra:
                            acq[disp] |= extra
                            changed = True
                        if res[disp] is None and res.get(gdisp) is not None:
                            res[disp] = f"via {g.qualname}()"
                            changed = True
        self.acquires_trans = acq
        self.resolves_trans = res
        cb_seen: Set[int] = set()
        for ev in self.events.values():
            for reg in ev.registers:
                for t in reg.targets:
                    if id(t) not in cb_seen:
                        cb_seen.add(id(t))
                        self.callbacks.append(t)

    def _resolution_sites(self, ev: FunctionEvents) -> List[Tuple[int, Tuple[str, ...], str]]:
        """(line, held, how) for every point in ``ev.fi`` where a Future may
        resolve while at least one lock is held."""
        out: List[Tuple[int, Tuple[str, ...], str]] = []
        for r in ev.resolves:
            if r.held:
                out.append((r.line, r.held, f"{r.receiver}.{r.method}()"))
        for call in ev.calls:
            if not call.held:
                continue
            for g in call.targets:
                how = self.resolves_trans.get(g.display)
                if how is not None:
                    out.append(
                        (call.line, call.held, f"call to {g.qualname}() ({how})")
                    )
        return out

    # --------------------------------------------------------------- DABT101
    def check_lock_order(self) -> List[Finding]:
        edges: Dict[Tuple[str, str], Tuple[FunctionEvents, int, str]] = {}

        def add(a: str, b: str, ev: FunctionEvents, line: int, via: str) -> None:
            if a != b and (a, b) not in edges:
                edges[(a, b)] = (ev, line, via)

        for ev in self.events.values():
            for acqev in ev.acquires:
                for h in acqev.held:
                    add(h, acqev.lock, ev, acqev.line, "nested acquisition")
            for call in ev.calls:
                if not call.held:
                    continue
                for g in call.targets:
                    for lock in self.acquires_trans.get(g.display, ()):
                        for h in call.held:
                            add(h, lock, ev, call.line, f"call to {g.qualname}()")
            for line, held, how in self._resolution_sites(ev):
                for cb in self.callbacks:
                    for lock in self.acquires_trans.get(cb.display, ()):
                        for h in held:
                            add(
                                h,
                                lock,
                                ev,
                                line,
                                f"{how} -> done-callback {cb.qualname}()",
                            )
        return self._cycles(edges)

    def _cycles(
        self, edges: Dict[Tuple[str, str], Tuple[FunctionEvents, int, str]]
    ) -> List[Finding]:
        graph: Dict[str, Set[str]] = {}
        for a, b in edges:
            graph.setdefault(a, set()).add(b)
            graph.setdefault(b, set())
        sccs = _tarjan(graph)
        findings = []
        for scc in sccs:
            if len(scc) < 2:
                continue
            cyc = _one_cycle(graph, scc)
            if not cyc:
                continue
            # canonical rotation: start at the smallest lock id
            start = cyc.index(min(cyc))
            cyc = cyc[start:] + cyc[:start]
            display = " -> ".join(_short_lock(c) for c in cyc + [cyc[0]])
            legs = []
            first_site = None
            for a, b in zip(cyc, cyc[1:] + [cyc[0]]):
                ev, line, via = edges[(a, b)]
                legs.append(
                    f"{_short_lock(a)} -> {_short_lock(b)} "
                    f"[{ev.fi.module.relpath}:{line} {ev.fi.qualname}, {via}]"
                )
                if first_site is None:
                    first_site = (ev, line)
            ev, line = first_site
            findings.append(
                Finding(
                    "DABT101",
                    ev.fi.module.relpath,
                    ev.fi.qualname,
                    f"lock-order cycle {display}; legs: " + "; ".join(legs),
                    line,
                )
            )
        return _dedupe(findings)

    # --------------------------------------------------------------- DABT102
    def check_future_under_lock(self) -> List[Finding]:
        findings = []
        for ev in self.events.values():
            for line, held, how in self._resolution_sites(ev):
                held_disp = ", ".join(sorted(_short_lock(h) for h in held))
                findings.append(
                    Finding(
                        "DABT102",
                        ev.fi.module.relpath,
                        ev.fi.qualname,
                        f"{how} while holding {held_disp}",
                        line,
                    )
                )
        return _dedupe(findings)

    # --------------------------------------------------------------- DABT103
    def check_async_blocking(self) -> List[Finding]:
        findings = []
        for ev in self.events.values():
            fi = ev.fi
            if not fi.is_async:
                continue
            for call, display, awaited in _async_body_calls(fi.node):
                if awaited:
                    continue
                desc = None
                if display in RAW_TIME_CALLS and display.endswith("sleep"):
                    desc = "time.sleep() blocks the event loop"
                elif (
                    display == "sleep"
                    and fi.module.imports.get("sleep") == "time.sleep"
                ):
                    desc = "time.sleep() blocks the event loop"
                elif display.startswith("subprocess.") or display == "os.system":
                    desc = f"{display}() runs a blocking subprocess"
                elif display.startswith(BLOCKING_HTTP_PREFIXES):
                    desc = f"{display}() is synchronous HTTP"
                elif display.endswith(".acquire") or display == "acquire":
                    if not _acquire_is_timed(call):
                        desc = f"{display}() without a timeout can block forever"
                if desc is not None:
                    findings.append(
                        Finding(
                            "DABT103",
                            fi.module.relpath,
                            fi.qualname,
                            f"{desc} inside async def",
                            call.lineno,
                        )
                    )
        return _dedupe(findings)

    # --------------------------------------------------------------- DABT104
    def check_hot_path_syncs(self) -> List[Finding]:
        roots: Dict[str, str] = {}  # display -> root qualname
        order: List[str] = []
        for disp, ev in self.events.items():
            q = ev.fi.qualname
            if any(
                fnmatch.fnmatch(q, pat) or fnmatch.fnmatch(disp, pat)
                for pat in HOT_PATH_PATTERNS
            ):
                roots[disp] = q
                order.append(disp)
        reach: Dict[str, str] = {}
        for root in sorted(order):
            stack = [root]
            while stack:
                disp = stack.pop()
                if disp in reach:
                    continue
                reach[disp] = roots[root]
                ev = self.events.get(disp)
                if ev is None:
                    continue
                for call in ev.calls:
                    for g in call.targets:
                        if g.display not in reach:
                            stack.append(g.display)
        findings = []
        for disp, root in reach.items():
            ev = self.events.get(disp)
            if ev is None:
                continue
            for desc, line in _host_sync_sites(ev.fi):
                findings.append(
                    Finding(
                        "DABT104",
                        ev.fi.module.relpath,
                        ev.fi.qualname,
                        f"{desc} reachable from hot path {root}",
                        line,
                    )
                )
        return _dedupe(findings)

    # --------------------------------------------------------------- DABT105
    def check_raw_time(self) -> List[Finding]:
        findings = []
        for m in self.project.modules:
            parts = m.relpath.split("/")
            if not any(d in parts for d in CLOCK_DISCIPLINE_DIRS):
                continue
            if not _module_has_clock_convention(m):
                continue
            for fi in m.functions.values():
                for node in _walk_own_body(fi.node):
                    if not isinstance(node, ast.Call):
                        continue
                    display = _expr_display(node.func)
                    bare = m.imports.get(display, "")
                    if display in RAW_TIME_CALLS or bare in RAW_TIME_CALLS:
                        name = display if display in RAW_TIME_CALLS else bare
                        findings.append(
                            Finding(
                                "DABT105",
                                m.relpath,
                                fi.qualname,
                                f"raw {name}() in a clock-disciplined module",
                                node.lineno,
                            )
                        )
        return _dedupe(findings)

    # ------------------------------------------------------------------- all
    def run(self, select: Optional[Set[str]] = None) -> List[Finding]:
        checks = {
            "DABT101": self.check_lock_order,
            "DABT102": self.check_future_under_lock,
            "DABT103": self.check_async_blocking,
            "DABT104": self.check_hot_path_syncs,
            "DABT105": self.check_raw_time,
        }
        out: List[Finding] = []
        for code, fn in checks.items():
            if select is None or code in select:
                out.extend(fn())
        return sorted(out, key=lambda f: (f.module, f.line, f.code, f.detail))


def run_analysis(
    roots: Sequence[str],
    *,
    base_dir: Optional[str] = None,
    select: Optional[Set[str]] = None,
) -> List[Finding]:
    project = Project.load(roots, base_dir=base_dir)
    return Analysis(project).run(select)


# ----------------------------------------------------------------- helpers
def _walk_own_body(fnode: ast.AST):
    """Walk a function's OWN body, skipping nested function/lambda subtrees —
    those are enumerated as their own FunctionInfos (or deferred payloads),
    and walking them here would double-report every site inside them."""
    stack = list(ast.iter_child_nodes(fnode))
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _module_has_clock_convention(m) -> bool:
    """The module opted into injectable time: some function takes a ``clock``
    or ``sleep`` parameter, or some class carries self._clock/self._sleep."""
    for fi in m.functions.values():
        args = fi.node.args
        for a in list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs):
            if a.arg in ("clock", "sleep"):
                return True
    for node in ast.walk(m.tree):
        if (
            isinstance(node, ast.Attribute)
            and node.attr in ("_clock", "_sleep")
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
        ):
            return True
    return False


def _async_body_calls(node: ast.AST):
    """(call, display, awaited) for the async function's own body, skipping
    nested function/lambda bodies (they run elsewhere)."""
    awaited_ids = set()
    stack = list(ast.iter_child_nodes(node))
    flat = []
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        if isinstance(n, ast.Await) and isinstance(n.value, ast.Call):
            awaited_ids.add(id(n.value))
        if isinstance(n, ast.Call):
            flat.append(n)
        stack.extend(ast.iter_child_nodes(n))
    for call in flat:
        yield call, _expr_display(call.func), id(call) in awaited_ids


def _host_sync_sites(fi: FunctionInfo):
    """(description, line) for device->host syncs in one function, with a
    local taint pass so float()/int() only fire on values that flowed from a
    jnp/jax expression in the same function."""
    tainted: Set[str] = set()

    def expr_tainted(e: ast.AST) -> bool:
        for sub in ast.walk(e):
            if isinstance(sub, ast.Name) and (
                sub.id in tainted or sub.id in ("jnp", "jax")
            ):
                return True
        return False

    # forward taint pass in statement order (_walk_own_body is close enough:
    # the function bodies we care about assign before use)
    for stmt in _walk_own_body(fi.node):
        if isinstance(stmt, ast.Assign) and expr_tainted(stmt.value):
            for tgt in stmt.targets:
                for sub in ast.walk(tgt):
                    if isinstance(sub, ast.Name):
                        tainted.add(sub.id)
    imports = fi.module.imports
    for node in _walk_own_body(fi.node):
        if not isinstance(node, ast.Call):
            continue
        display = _expr_display(node.func)
        # canonicalize the root through the import table, so aliased imports
        # (`import numpy as _np`) cannot dodge the checker
        root, dot, rest = display.partition(".")
        canonical = f"{imports.get(root, root)}{dot}{rest}"
        if isinstance(node.func, ast.Attribute) and node.func.attr in HOST_SYNC_METHODS:
            yield f"{display}() forces a device->host sync", node.lineno
        elif canonical in HOST_SYNC_CALLS:
            yield f"{display}() copies device memory to host", node.lineno
        elif (
            display in ("float", "int")
            and len(node.args) == 1
            and expr_tainted(node.args[0])
        ):
            yield (
                f"{display}() of a traced/device value forces a host sync",
                node.lineno,
            )


def _tarjan(graph: Dict[str, Set[str]]) -> List[List[str]]:
    index: Dict[str, int] = {}
    low: Dict[str, int] = {}
    on_stack: Set[str] = set()
    stack: List[str] = []
    out: List[List[str]] = []
    counter = [0]

    def strongconnect(v: str) -> None:
        # iterative Tarjan (engine-sized call graphs overflow recursion)
        work = [(v, iter(sorted(graph.get(v, ()))))]
        index[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        while work:
            node, it = work[-1]
            advanced = False
            for w in it:
                if w not in index:
                    index[w] = low[w] = counter[0]
                    counter[0] += 1
                    stack.append(w)
                    on_stack.add(w)
                    work.append((w, iter(sorted(graph.get(w, ())))))
                    advanced = True
                    break
                elif w in on_stack:
                    low[node] = min(low[node], index[w])
            if advanced:
                continue
            work.pop()
            if work:
                low[work[-1][0]] = min(low[work[-1][0]], low[node])
            if low[node] == index[node]:
                scc = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                out.append(scc)

    for v in sorted(graph):
        if v not in index:
            strongconnect(v)
    return out


def _one_cycle(graph: Dict[str, Set[str]], scc: List[str]) -> List[str]:
    """One simple cycle inside an SCC, for display."""
    members = set(scc)
    start = min(scc)
    path = [start]
    seen = {start}
    node = start
    while True:
        nxt = None
        for w in sorted(graph.get(node, ())):
            if w == start and len(path) > 1:
                return path
            if w in members and w not in seen:
                nxt = w
                break
        if nxt is None:
            # backtrack-free walk failed (rare); fall back to any 2-cycle
            for a in sorted(members):
                for b in sorted(graph.get(a, ())):
                    if b in members and a in graph.get(b, set()):
                        return [a, b]
            return []
        path.append(nxt)
        seen.add(nxt)
        node = nxt
