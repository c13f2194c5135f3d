"""edl-lint suite tests (tier-1).

Per-rule positive/negative fixture trees prove each family fires on a
violation and stays silent on the clean twin; the CLI tests prove both
exit-code directions; the repo tests pin the conformance invariants
the lint exists to hold (every called method has a handler, the retry
classification matches rpc/policy.py, the live tree is lint-clean).
"""

import ast
import json
import os
import subprocess
import sys

import pytest

from elasticdl_tpu.analysis import RULE_FAMILIES, run_analysis
from elasticdl_tpu.analysis.__main__ import main as lint_main
from elasticdl_tpu.analysis.core import load_baseline, load_context
from elasticdl_tpu.analysis import abort_discipline as ad
from elasticdl_tpu.analysis import callgraph as cg
from elasticdl_tpu.analysis import fencing_conformance as fc
from elasticdl_tpu.analysis import lock_order as lo
from elasticdl_tpu.analysis import resource_lifecycle as rl
from elasticdl_tpu.analysis import rpc_conformance as rc
from elasticdl_tpu.analysis import thread_provenance as tp

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG_ROOT = os.path.join(REPO_ROOT, "elasticdl_tpu")
FIXTURE_DIR = os.path.join(REPO_ROOT, "tests", "fixtures", "analysis")


def _fixture(name):
    with open(os.path.join(FIXTURE_DIR, name), encoding="utf-8") as f:
        return f.read()


def _tree(tmp_path, files):
    for rel, source in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(source)
    return str(tmp_path)


def _checks(findings, rule):
    return {f.check for f in findings if f.rule == rule}


# -- rpc-conformance ---------------------------------------------------------

RPC_GOOD = """
class S:
    def handlers(self):
        return {"Ping": self.ping}

    def ping(self, req):
        return {"x": req.get("x")}


def go(client):
    client.call("Ping", {"x": 1})
"""

RPC_BAD_NO_HANDLER = """
class S:
    def handlers(self):
        return {"Ping": self.ping}

    def ping(self, req):
        return {}


def go(client):
    client.call("Ping", {})
    client.call("Pong", {"x": 1})
"""

RPC_BAD_SCHEMA = """
import dataclasses


@dataclasses.dataclass
class PingRequest:
    x: int = 0


WIRE_SCHEMAS = {"Ping": PingRequest}


class S:
    def handlers(self):
        return {"Ping": self.ping}

    def ping(self, req):
        return {"a": req["x"], "b": req.get("ghost")}


def go(client):
    client.call("Ping", {"x": 1, "bogus": 2})
"""

RPC_BAD_POLICY = """
IDEMPOTENT_METHODS = frozenset({"Ping", "Phantom"})
DEDUP_KEYED_METHODS = {"Push"}


class S:
    def handlers(self):
        return {"Ping": self.ping, "Push": self.push}

    def ping(self, req):
        return {}

    def push(self, req):
        return {}


def go(client):
    client.call("Ping", {})
    client.call("Push", {"grad": 1})
    client.call("Ping", {}, idempotent=True)
"""


def test_rpc_conformance_clean(tmp_path):
    root = _tree(tmp_path, {"mod.py": RPC_GOOD})
    assert run_analysis(root, rules=["rpc-conformance"]) == []


def test_rpc_conformance_no_handler_and_unused(tmp_path):
    root = _tree(tmp_path, {"mod.py": RPC_BAD_NO_HANDLER})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "no-handler" in checks  # Pong called, never registered


def test_rpc_conformance_unused_handler(tmp_path):
    src = RPC_BAD_NO_HANDLER.replace('client.call("Pong", {"x": 1})', "pass")
    src = src.replace('client.call("Ping", {})\n', "")
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "unused-handler" in checks


def test_rpc_conformance_schema_keys(tmp_path):
    root = _tree(tmp_path, {"mod.py": RPC_BAD_SCHEMA})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "unknown-request-key" in checks  # call sends 'bogus'
    assert "handler-unknown-key" in checks  # handler reads 'ghost'


def test_rpc_conformance_policy_checks(tmp_path):
    root = _tree(tmp_path, {"mod.py": RPC_BAD_POLICY})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "idempotent-no-handler" in checks  # Phantom classified, unregistered
    assert "dedup-not-idempotent" in checks  # Push dedup-keyed, not idempotent
    assert "missing-dedup-key" in checks  # Push request lacks report_key


def test_rpc_conformance_retry_unclassified(tmp_path):
    src = RPC_BAD_POLICY.replace(
        'IDEMPOTENT_METHODS = frozenset({"Ping", "Phantom"})',
        'IDEMPOTENT_METHODS = frozenset({"Push"})',
    ).replace('DEDUP_KEYED_METHODS = {"Push"}', "DEDUP_KEYED_METHODS = set()")
    src = src.replace('client.call("Push", {"grad": 1})', "pass")
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "retry-unclassified" in checks  # idempotent=True outside the set


def test_rpc_conformance_executor_form(tmp_path):
    src = RPC_BAD_NO_HANDLER.replace(
        'client.call("Pong", {"x": 1})',
        'pool.submit(client.call, "Pong", {"x": 1})',
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "no-handler" in checks


def test_rpc_conformance_dynamic_request_skipped(tmp_path):
    # an unresolvable request dict must be skipped, not guessed at
    src = RPC_BAD_SCHEMA.replace(
        'client.call("Ping", {"x": 1, "bogus": 2})',
        'client.call("Ping", build_request())',
    ).replace('"b": req.get("ghost")', '"b": 0')
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance")
    assert "unknown-request-key" not in checks


FRAME_GOOD = """
FRAME_DESCRIPTOR_FIELDS = ("d", "s", "o", "n")


def _frame_descriptor(a, builder):
    return {"d": "dt", "s": [1], "o": 0, "n": 4}


def _read_frame_descriptor(m, frame, payload_start):
    return (m["d"], m["s"], m["o"], m["n"])
"""


def test_frame_descriptor_contract_clean(tmp_path):
    root = _tree(tmp_path, {"codec.py": FRAME_GOOD})
    assert run_analysis(root, rules=["rpc-conformance"]) == []


def test_frame_descriptor_emit_drift(tmp_path):
    # encoder grows a field the declaration doesn't know about
    src = FRAME_GOOD.replace('"n": 4}', '"n": 4, "z": 9}')
    root = _tree(tmp_path, {"codec.py": src})
    checks = _checks(
        run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance"
    )
    assert "frame-emit-drift" in checks


def test_frame_descriptor_read_drift_both_ways(tmp_path):
    # decoder reads an undeclared key AND skips a declared one
    src = FRAME_GOOD.replace('m["n"])', 'm["ghost"])')
    root = _tree(tmp_path, {"codec.py": src})
    checks = _checks(
        run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance"
    )
    assert "frame-read-drift" in checks


def test_frame_descriptor_lints_the_real_codec():
    """The shipped codec must satisfy its own declared contract."""
    import elasticdl_tpu

    root = os.path.dirname(elasticdl_tpu.__file__)
    findings = run_analysis(root, rules=["rpc-conformance"])
    assert not [
        f for f in findings if f.check.startswith("frame-")
    ], findings


# -- rpc-conformance: transport tier registry --------------------------------

TRANSPORT_GOOD = """
TRANSPORT_UDS = "uds"
TRANSPORT_TIERS = ("grpc", TRANSPORT_UDS, "inproc")


def transport_faults_before(plan, method, side):
    return []


def transport_faults_after(after, method):
    pass


class ServerDispatcher:
    def dispatch(self, method, request_bytes, transport):
        after = transport_faults_before(None, method, "server")
        resp = b""
        transport_faults_after(after, method)
        return resp


class UdsTransport:
    name = TRANSPORT_UDS

    def call(self, method, payload, timeout):
        after = transport_faults_before(None, method, "client")
        transport_faults_after(after, method)
        return b""


class UdsServer:
    def serve(self, dispatcher, method, body):
        return dispatcher.dispatch(method, body, "uds")
"""


def test_transport_registry_clean(tmp_path):
    root = _tree(tmp_path, {"transport.py": TRANSPORT_GOOD})
    assert run_analysis(root, rules=["rpc-conformance"]) == []


def test_transport_surface_drift(tmp_path):
    # one tier renames an argument; another registers an unknown tier
    src = TRANSPORT_GOOD.replace(
        "def call(self, method, payload, timeout):",
        "def call(self, method, body, timeout):",
    ).replace('name = TRANSPORT_UDS', 'name = "carrier-pigeon"')
    root = _tree(tmp_path, {"transport.py": src})
    findings = run_analysis(root, rules=["rpc-conformance"])
    drift = [f for f in findings if f.check == "transport-surface-drift"]
    assert len(drift) == 2, findings


def test_transport_missing_call_is_surface_drift(tmp_path):
    src = TRANSPORT_GOOD.replace(
        "    def call(self, method, payload, timeout):\n"
        "        after = transport_faults_before(None, method, \"client\")\n"
        "        transport_faults_after(after, method)\n"
        "        return b\"\"\n",
        "    pass\n",
    )
    root = _tree(tmp_path, {"transport.py": src})
    checks = _checks(
        run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance"
    )
    assert "transport-surface-drift" in checks


def test_transport_chaos_bypass_client_and_server(tmp_path):
    # the client tier forgets the before-hook, the dispatcher the after
    src = TRANSPORT_GOOD.replace(
        'after = transport_faults_before(None, method, "client")\n'
        "        transport_faults_after(after, method)",
        "pass",
    ).replace(
        'after = transport_faults_before(None, method, "server")',
        "after = []",
    )
    root = _tree(tmp_path, {"transport.py": src})
    findings = run_analysis(root, rules=["rpc-conformance"])
    bypass = [f for f in findings if f.check == "transport-chaos-bypass"]
    assert len(bypass) == 2, findings


def test_transport_dispatch_bypass(tmp_path):
    # a listener serving its own method table instead of the dispatcher
    src = TRANSPORT_GOOD.replace(
        'return dispatcher.dispatch(method, body, "uds")',
        "return self.handlers[method](body)",
    )
    root = _tree(tmp_path, {"transport.py": src})
    checks = _checks(
        run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance"
    )
    assert "transport-dispatch-bypass" in checks


def test_transport_lints_the_real_tree():
    """The shipped tier registry must satisfy its own contract: same
    call surface per tier, chaos hooks on every path, every listener
    funneled through ServerDispatcher."""
    import elasticdl_tpu

    root = os.path.dirname(elasticdl_tpu.__file__)
    findings = run_analysis(root, rules=["rpc-conformance"])
    assert not [
        f for f in findings if f.check.startswith("transport-")
    ], findings


# aggregator forward-path twin (agg/aggregator.py): workers push on the
# dedup-keyed AggPushDelta surface through a dispatcher-routed listener,
# the presummed cohort forwards upstream as ONE PSPushDeltaCombined
# frame (member report_keys riding along) over a chaos-hooked client
# tier — the two places the tree could silently drop out of the fault
# plane are the ring listener and the upstream hop, so both get pos/neg
# fixtures here
AGG_FORWARD_GOOD = """
IDEMPOTENT_METHODS = frozenset({"AggPushDelta"})
DEDUP_KEYED_METHODS = {"AggPushDelta"}

TRANSPORT_TIERS = ("uds", "inproc")


def transport_faults_before(plan, method, side):
    return []


def transport_faults_after(after, method):
    pass


class ServerDispatcher:
    def dispatch(self, method, request_bytes, transport):
        after = transport_faults_before(None, method, "server")
        resp = b""
        transport_faults_after(after, method)
        return resp


class UpstreamTransport:
    name = "uds"

    def call(self, method, payload, timeout):
        after = transport_faults_before(None, method, "client")
        transport_faults_after(after, method)
        return b""


class AggRingServer:
    def serve_conn(self, dispatcher, method, body):
        return dispatcher.dispatch(method, body, "uds")


class PSShardServicer:
    def handlers(self):
        return {"PSPushDeltaCombined": self.push_delta_combined}

    def push_delta_combined(self, req):
        return {"accepted": True}


class AggregatorServicer:
    def handlers(self):
        return {"AggPushDelta": self.push_delta}

    def push_delta(self, req):
        return {"k": req.get("report_key")}

    def forward(self, upstream, keys):
        upstream.call(
            "PSPushDeltaCombined",
            {"delta": b"", "steps": 2, "report_keys": keys},
        )


def worker_push(client, key):
    client.call("AggPushDelta", {"delta": b"", "report_key": key})
"""


def test_agg_forward_path_clean(tmp_path):
    """Negative fixture: the conforming aggregator forward path —
    keyed member pushes, dispatcher-routed worker-facing listener,
    chaos-hooked upstream tier, combined frame with a registered
    handler — is lint-silent."""
    root = _tree(tmp_path, {"agg.py": AGG_FORWARD_GOOD})
    assert run_analysis(root, rules=["rpc-conformance"]) == []


def test_agg_forward_upstream_chaos_bypass(tmp_path):
    # the upstream hop skips FaultPlan injection: the one combined
    # frame per cohort is exactly the call chaos e2e must reach
    src = AGG_FORWARD_GOOD.replace(
        'after = transport_faults_before(None, method, "client")\n'
        "        transport_faults_after(after, method)",
        "pass",
    )
    root = _tree(tmp_path, {"agg.py": src})
    findings = run_analysis(root, rules=["rpc-conformance"])
    bypass = [f for f in findings if f.check == "transport-chaos-bypass"]
    assert len(bypass) == 1, findings
    assert "UpstreamTransport" in bypass[0].message


def test_agg_forward_listener_dispatch_bypass(tmp_path):
    # an aggregator listener decoding worker pushes into its own
    # method table instead of ServerDispatcher — admission queues,
    # fencing, and server-side chaos would all silently vanish from
    # the worker-facing leg
    src = AGG_FORWARD_GOOD.replace(
        'return dispatcher.dispatch(method, body, "uds")',
        "return self.handlers[method](body)",
    )
    root = _tree(tmp_path, {"agg.py": src})
    findings = run_analysis(root, rules=["rpc-conformance"])
    bypass = [
        f for f in findings if f.check == "transport-dispatch-bypass"
    ]
    assert len(bypass) == 1, findings
    assert "AggRingServer" in bypass[0].message


def test_agg_forward_unkeyed_member_push_flagged(tmp_path):
    # a worker push without report_key: a retry after an ambiguous
    # failure would double-apply at the aggregator
    src = AGG_FORWARD_GOOD.replace(
        '{"delta": b"", "report_key": key}', '{"delta": b""}'
    )
    root = _tree(tmp_path, {"agg.py": src})
    checks = _checks(
        run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance"
    )
    assert "missing-dedup-key" in checks


def test_agg_forward_unregistered_upstream_method(tmp_path):
    # the forward targets a method no servicer registers — the cohort
    # would die with UNIMPLEMENTED at the PS boundary
    src = AGG_FORWARD_GOOD.replace(
        'upstream.call(\n            "PSPushDeltaCombined",',
        'upstream.call(\n            "PSPushCombined",',
    )
    root = _tree(tmp_path, {"agg.py": src})
    checks = _checks(
        run_analysis(root, rules=["rpc-conformance"]), "rpc-conformance"
    )
    assert "no-handler" in checks


# -- lock-discipline ---------------------------------------------------------

LOCK_BAD = """
import threading
import time


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def bump(self):
        with self._lock:
            self._n += 1

    def peek(self):
        return self._n

    def slow_bump(self):
        with self._lock:
            time.sleep(0.1)
            self._n += 1
"""

LOCK_GOOD = """
import threading


class Counter:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0

    def bump(self):
        with self._lock:
            self._n += 1

    def peek(self):
        with self._lock:
            return self._n
"""


def test_lock_discipline_flags_unguarded_and_blocking(tmp_path):
    root = _tree(tmp_path, {"mod.py": LOCK_BAD})
    findings = run_analysis(root, rules=["lock-discipline"])
    checks = _checks(findings, "lock-discipline")
    assert "unguarded-access" in checks  # peek reads self._n lock-free
    assert "blocking-under-lock" in checks  # time.sleep inside the lock


def test_lock_discipline_clean(tmp_path):
    root = _tree(tmp_path, {"mod.py": LOCK_GOOD})
    assert run_analysis(root, rules=["lock-discipline"]) == []


def test_lock_discipline_suppression_covers_def(tmp_path):
    src = LOCK_BAD.replace(
        "    def peek(self):",
        "    def peek(self):  # edl-lint: disable=lock-discipline -- benign racy read",
    )
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["lock-discipline"])
    assert "unguarded-access" not in {
        f.check for f in findings if "peek" in f.message
    }


LOCK_COND = """
import threading


class Queue:
    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._items = []
        self._done = 0

    def put(self, x):
        with self._cond:
            self._items.append(x)
            self._cond.notify()

    def take(self):
        with self._lock:
            while not self._items:
                self._cond.wait()
            return self._items.pop()

    def flush(self):
        with self._cond:
            self._cond.wait_for(lambda: self._done >= len(self._items))

    def mark(self):
        with self._lock:
            self._done += 1
"""


def test_lock_discipline_condition_aliases_to_wrapped_lock(tmp_path):
    # with self._cond: IS holding self._lock (Condition(self._lock)),
    # cond.wait() under the condition releases the lock (not a
    # blocking-under-lock), and a wait_for predicate lambda runs with
    # the lock re-acquired — the whole fixture is clean
    root = _tree(tmp_path, {"mod.py": LOCK_COND})
    assert run_analysis(root, rules=["lock-discipline"]) == []


def test_lock_discipline_bare_condition_guards_itself(tmp_path):
    # Condition() with no wrapped lock owns its own lock, distinct from
    # self._lock: flush's predicate now reads _done under the WRONG
    # guard (mark writes it under _lock), and take waits on a condition
    # it does NOT hold while holding _lock — both silent in the aliased
    # original, both real once the condition stops wrapping the lock
    src = LOCK_COND.replace(
        "self._cond = threading.Condition(self._lock)",
        "self._cond = threading.Condition()",
    )
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["lock-discipline"])
    assert any(
        f.check == "unguarded-access" and "_done" in f.message
        for f in findings
    )
    assert any(f.check == "blocking-under-lock" for f in findings)


def test_lock_discipline_foreign_condition_wait_still_blocks(tmp_path):
    # waiting on someone ELSE's condition while holding your lock is a
    # real stall — only the held condition's own wait is exempt
    src = LOCK_GOOD.replace(
        "    def peek(self):\n        with self._lock:\n"
        "            return self._n",
        "    def peek(self, other):\n        with self._lock:\n"
        "            other.wait()\n            return self._n",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["lock-discipline"]), "lock-discipline"
    )
    assert "blocking-under-lock" in checks


LOCK_DECLARED = """
import threading


class Pipeline:
    SYNC_GUARDED_ATTRS = {"_lock": ("_staged",)}

    def __init__(self):
        self._lock = threading.Lock()
        self._staged = None

    def stage(self, x):
        with self._lock:
            self._staged = x

    def peek(self):
        return self._staged
"""


def test_lock_discipline_declared_attrs_flag_bare_reads(tmp_path):
    # the SYNC_GUARDED_ATTRS declaration makes _staged guarded even
    # when write-site inference alone would agree; the bare peek is a
    # finding
    root = _tree(tmp_path, {"mod.py": LOCK_DECLARED})
    findings = run_analysis(root, rules=["lock-discipline"])
    assert any(
        f.check == "unguarded-access" and "_staged" in f.message
        for f in findings
    ), findings


def test_lock_discipline_declared_attrs_need_no_write_sites(tmp_path):
    # the declaration's whole point: a background thread writes the
    # attr through a helper the inferencer can't see (here: no in-class
    # write under the lock AT ALL), yet the bare read must still flag.
    # Without the declaration this exact source is silent.
    src = LOCK_DECLARED.replace(
        "    def stage(self, x):\n"
        "        with self._lock:\n"
        "            self._staged = x\n",
        "",
    )
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["lock-discipline"])
    assert any(
        f.check == "unguarded-access" and "_staged" in f.message
        for f in findings
    ), findings
    # negative control: the same class minus the declaration is clean
    undeclared = src.replace(
        '    SYNC_GUARDED_ATTRS = {"_lock": ("_staged",)}\n', ""
    )
    root2 = _tree(tmp_path / "b", {"mod.py": undeclared})
    assert run_analysis(root2, rules=["lock-discipline"]) == []


def test_lock_discipline_declared_attrs_clean_when_guarded(tmp_path):
    src = LOCK_DECLARED.replace(
        "    def peek(self):\n        return self._staged",
        "    def peek(self):\n        with self._lock:\n"
        "            return self._staged",
    )
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["lock-discipline"]) == []


def test_lock_discipline_declared_unknown_lock_is_flagged(tmp_path):
    # declaring a guard the class never creates is a spec bug, not a
    # silent no-op
    src = LOCK_DECLARED.replace(
        'SYNC_GUARDED_ATTRS = {"_lock": ("_staged",)}',
        'SYNC_GUARDED_ATTRS = {"_lokc": ("_staged",)}',
    )
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["lock-discipline"])
    assert any(
        f.check == "bad-guard-declaration" and "_lokc" in f.message
        for f in findings
    ), findings


def test_suppression_requires_reason(tmp_path):
    src = LOCK_BAD.replace(
        "    def peek(self):",
        "    def peek(self):  # edl-lint: disable=lock-discipline",
    )
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["lock-discipline"])
    checks = {(f.rule, f.check) for f in findings}
    # the reasonless suppression is itself a finding AND does not suppress
    assert ("lint", "suppression-missing-reason") in checks
    assert ("lock-discipline", "unguarded-access") in checks


def test_suppression_unknown_rule_is_flagged(tmp_path):
    src = LOCK_GOOD.replace(
        "    def peek(self):",
        "    def peek(self):  # edl-lint: disable=made-up-rule -- because",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = {(f.rule, f.check) for f in run_analysis(root)}
    assert ("lint", "unknown-suppressed-rule") in checks


# -- jit-purity --------------------------------------------------------------

JIT_BAD = """
import time

import jax


@jax.jit
def stamped(x):
    return x + time.time()


acc = []


def log_step(x):
    acc.append(x)
    return x


log_jit = jax.jit(log_step)
"""

JIT_GOOD = """
import jax


@jax.jit
def double(x):
    return x * 2


def build(tx):
    def step(params, state, grads):
        updates, state = tx.update(grads, state, params)
        scales = {}
        scales["lr"] = 1.0
        return params + updates * scales["lr"], state

    return jax.jit(step)
"""


def test_jit_purity_flags_impure_and_captured(tmp_path):
    root = _tree(tmp_path, {"mod.py": JIT_BAD})
    checks = _checks(run_analysis(root, rules=["jit-purity"]), "jit-purity")
    assert "impure-call" in checks  # time.time under trace
    assert "captured-mutation" in checks  # acc.append from outer scope


def test_jit_purity_clean_functional_update(tmp_path):
    # optax-style consumed .update() and within-trace dict stores are pure
    root = _tree(tmp_path, {"mod.py": JIT_GOOD})
    assert run_analysis(root, rules=["jit-purity"]) == []


def test_jit_purity_partial_decorator(tmp_path):
    src = """
import functools

import jax


@functools.partial(jax.jit, static_argnums=(1,))
def f(x, n):
    print(x)
    return x * n
"""
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(run_analysis(root, rules=["jit-purity"]), "jit-purity")
    assert "impure-call" in checks


# -- env-registry ------------------------------------------------------------

ENV_GOOD = """
import os

ENV_FOO = "EDL_FOO"
ENV_REGISTRY = {ENV_FOO: "a declared knob"}


def read():
    return os.getenv(ENV_FOO, "0")
"""

ENV_BAD = ENV_GOOD + """

def sneak():
    return os.environ.get("EDL_SNEAKY")
"""


def test_env_registry_clean(tmp_path):
    root = _tree(tmp_path, {"mod.py": ENV_GOOD})
    assert run_analysis(root, rules=["env-registry"]) == []


def test_env_registry_flags_undeclared(tmp_path):
    root = _tree(tmp_path, {"mod.py": ENV_BAD})
    findings = run_analysis(root, rules=["env-registry"])
    assert _checks(findings, "env-registry") == {"undeclared-env-var"}
    assert any("EDL_SNEAKY" in f.message for f in findings)


def test_env_registry_no_registry(tmp_path):
    src = 'import os\n\nV = os.getenv("EDL_ORPHAN")\n'
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(run_analysis(root, rules=["env-registry"]), "env-registry")
    assert checks == {"no-registry"}


def test_env_registry_ignores_unprefixed(tmp_path):
    src = 'import os\n\nV = os.getenv("PATH")\n'
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["env-registry"]) == []


# -- metric-registry ----------------------------------------------------------
# fixtures are real files so the obs docs can point at runnable examples

METRIC_GOOD = _fixture("metric_registry_good.py")
METRIC_BAD = _fixture("metric_registry_bad.py")


def test_metric_registry_clean(tmp_path):
    root = _tree(tmp_path, {"mod.py": METRIC_GOOD})
    assert run_analysis(root, rules=["metric-registry"]) == []


def test_metric_registry_flags_undeclared_and_obs_env(tmp_path):
    root = _tree(tmp_path, {"mod.py": METRIC_BAD})
    findings = run_analysis(root, rules=["metric-registry"])
    assert _checks(findings, "metric-registry") == {
        "undeclared-metric",
        "undeclared-obs-env",
    }
    assert any("edl_demo_sneaky_total" in f.message for f in findings)
    assert any("edl_demo_other_total" in f.message for f in findings)
    assert any("EDL_METRICS_PORT_SNEAKY" in f.message for f in findings)


def test_metric_registry_no_registry(tmp_path):
    src = 'def emit(reg):\n    reg.inc("edl_orphan_total")\n'
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["metric-registry"]), "metric-registry"
    )
    assert checks == {"no-metric-registry"}


def test_metric_registry_ignores_non_edl_and_computed(tmp_path):
    src = (
        'METRIC_REGISTRY = {"edl_x": "x"}\n'
        "\n\n"
        "def emit(reg, name):\n"
        '    reg.inc("requests_total")\n'  # unprefixed: someone else's
        "    reg.inc(name)\n"  # computed: not statically resolvable
    )
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["metric-registry"]) == []


# -- edl-verify: fencing-conformance ------------------------------------------
# the interprocedural families keep their fixtures as real files under
# tests/fixtures/analysis/ (positive + clean twin per rule)

FENCING_GOOD = _fixture("fencing_good.py")
FENCING_BAD = _fixture("fencing_bad.py")
LOCK_ORDER_GOOD = _fixture("lock_order_good.py")
LOCK_ORDER_BAD = _fixture("lock_order_bad.py")
ABORT_GOOD = _fixture("abort_good.py")
ABORT_BAD = _fixture("abort_bad.py")
ASYNC_GOOD = _fixture("async_good.py")
ASYNC_BAD = _fixture("async_bad.py")
THREAD_PROV_GOOD = _fixture("thread_provenance_good.py")
THREAD_PROV_BAD = _fixture("thread_provenance_bad.py")
EXACT_GOOD = _fixture("exactness_lineage_good.py")
EXACT_BAD = _fixture("exactness_lineage_bad.py")
RES_LIFE_GOOD = _fixture("resource_lifecycle_good.py")
RES_LIFE_BAD = _fixture("resource_lifecycle_bad.py")
SHUT_ORDER_GOOD = _fixture("shutdown_order_good.py")
SHUT_ORDER_BAD = _fixture("shutdown_order_bad.py")


def test_fencing_flags_unfenced_handler_and_call_site(tmp_path):
    root = _tree(tmp_path, {"mod.py": FENCING_BAD})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "unfenced-handler" in checks  # put mutates with no epoch check
    assert "unfenced-call-site" in checks  # Get called with no epoch
    assert "fenced-abort-missing" in checks  # nothing maps the rejection


def test_fencing_clean_under_all_rules(tmp_path):
    # literal-epoch call, _stamp_epoch wrapper, helper-mediated fence,
    # FAILED_PRECONDITION mapping: nothing to say, under any family
    root = _tree(tmp_path, {"mod.py": FENCING_GOOD})
    assert run_analysis(root) == []


def test_fencing_fence_after_mutation(tmp_path):
    src = FENCING_GOOD.replace(
        '        self._check_epoch(req)\n'
        '        self.rows[req["key"]] = req["value"]',
        '        self.rows[req["key"]] = req["value"]\n'
        '        self._check_epoch(req)',
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "fence-after-mutation" in checks


def test_fencing_declared_unfenced_exempts_handler(tmp_path):
    src = FENCING_BAD.replace(
        "    def handlers(self):",
        '    UNFENCED_HANDLERS = frozenset({"Put"})\n\n'
        "    def handlers(self):",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "unfenced-handler" not in checks  # declared by-design unfenced
    assert "unfenced-call-site" in checks  # the Get call site still fires


def test_fencing_declared_unfenced_stale(tmp_path):
    src = FENCING_GOOD.replace(
        "    def handlers(self):",
        '    UNFENCED_HANDLERS = frozenset({"Ghost"})\n\n'
        "    def handlers(self):",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "declared-unfenced-stale" in checks


def test_fencing_stamp_helper_inert(tmp_path):
    src = FENCING_GOOD.replace(
        '        req["epoch"] = self._epoch\n        return req',
        "        return req",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "stamp-helper-inert" in checks


def test_fencing_retryable_codes_guard(tmp_path):
    src = FENCING_GOOD + (
        "\n\nRETRYABLE_CODES = frozenset({StatusCode.FAILED_PRECONDITION})\n"
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "retryable-fenced-code" in checks


def test_fencing_wrong_abort_code(tmp_path):
    src = FENCING_GOOD.replace(
        "ctx.abort(StatusCode.FAILED_PRECONDITION, str(e))",
        "ctx.abort(StatusCode.INTERNAL, str(e))",
    ).replace(
        '    FAILED_PRECONDITION = "failed-precondition"',
        '    FAILED_PRECONDITION = "failed-precondition"\n'
        '    INTERNAL = "internal"',
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["fencing-conformance"]), "fencing-conformance"
    )
    assert "fenced-abort-wrong-code" in checks


# -- edl-verify: lock-order ----------------------------------------------------


def test_lock_order_flags_cycle_blocking_and_self_deadlock(tmp_path):
    root = _tree(tmp_path, {"mod.py": LOCK_ORDER_BAD})
    findings = run_analysis(root, rules=["lock-order"])
    checks = _checks(findings, "lock-order")
    # a->b via forward's callee, b->a via backward's: only visible
    # ACROSS the call boundary
    assert "lock-cycle" in checks
    assert "blocking-call-chain" in checks  # stall -> _slow -> time.sleep
    assert "self-deadlock" in checks  # re_enter -> _take_a re-acquires _a
    cycle = next(f for f in findings if f.check == "lock-cycle")
    assert "Pair._a" in cycle.message and "Pair._b" in cycle.message


def test_lock_order_clean_under_all_rules(tmp_path):
    # consistent order + RLock re-entry: silent under every family
    root = _tree(tmp_path, {"mod.py": LOCK_ORDER_GOOD})
    assert run_analysis(root) == []


def test_lock_order_direct_blocking_stays_lock_discipline(tmp_path):
    # the same-frame sleep-under-lock is lock-discipline's finding; the
    # interprocedural rule must not duplicate it
    root = _tree(tmp_path, {"mod.py": LOCK_BAD})
    assert run_analysis(root, rules=["lock-order"]) == []


def test_find_cycles_canonical():
    e = lambda *pairs: {p: ("m.py", 1, "via") for p in pairs}  # noqa: E731
    a, b, c = ("m::C", "a"), ("m::C", "b"), ("m::C", "c")
    assert lo._find_cycles(e((a, b), (b, a))) == [[a, b]]
    # one rotation per cycle, reported from its smallest member
    assert lo._find_cycles(e((b, c), (c, a), (a, b))) == [[a, b, c]]
    assert lo._find_cycles(e((a, b), (b, c))) == []


# -- edl-verify: abort-discipline ----------------------------------------------


def test_abort_discipline_flags_swallowing_helpers(tmp_path):
    root = _tree(tmp_path, {"mod.py": ABORT_BAD})
    findings = run_analysis(root, rules=["abort-discipline"])
    checks = _checks(findings, "abort-discipline")
    assert "swallowed-exception" in checks  # _run eats Exception
    assert "fence-swallowed" in checks  # _fenced eats EpochFencedError
    # both attributed to the registering handler, two frames up
    assert all("Work" in f.message for f in findings)


def test_abort_discipline_clean_under_all_rules(tmp_path):
    # re-raise and classified abort both discharge the obligation
    root = _tree(tmp_path, {"mod.py": ABORT_GOOD})
    assert run_analysis(root) == []


def test_abort_discipline_ignores_unreachable_code(tmp_path):
    # the same swallowing except outside any handler's call path is not
    # this rule's concern
    src = ABORT_BAD.replace('return {"Work": self.work}', "return {}")
    src = src.replace('client.call("Work", {"x": 1})', "pass")
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["abort-discipline"]) == []


def test_abort_discipline_suppression(tmp_path):
    src = ABORT_BAD.replace(
        "    def _run(self, req):",
        "    def _run(self, req):  # edl-lint: disable=abort-discipline -- deliberate sink for the test",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["abort-discipline"]), "abort-discipline"
    )
    assert checks == {"fence-swallowed"}  # only the unsuppressed one


# -- edl-verify: async-discipline ----------------------------------------------


def test_async_discipline_flags_loop_blockers_and_state_leak(tmp_path):
    root = _tree(tmp_path, {"mod.py": ASYNC_BAD})
    findings = run_analysis(root, rules=["async-discipline"])
    checks = _checks(findings, "async-discipline")
    assert "blocking-on-loop" in checks
    assert "loop-state-off-loop" in checks
    msgs = [f.message for f in findings]
    # the sync RPC two frames below the coroutine, found ACROSS calls
    assert any(
        '.call("Ping")' in m and "Listener.serve" in m for m in msgs
    )
    assert any("time.sleep" in m for m in msgs)  # direct coroutine sleep
    assert any(".acquire()" in m for m in msgs)  # unbounded lock park
    assert any("_writers" in m and "reset" in m for m in msgs)


def test_async_discipline_clean_under_all_rules(tmp_path):
    # awaited async APIs, the run_in_executor reference boundary,
    # bounded acquire, on-loop-only touches: silent under every family
    root = _tree(tmp_path, {"mod.py": ASYNC_GOOD})
    assert run_analysis(root) == []


def test_async_discipline_executor_reference_is_a_boundary(tmp_path):
    # calling the blocking half DIRECTLY (instead of passing it to
    # run_in_executor as a reference) puts it on the loop: must flag
    src = ASYNC_GOOD.replace(
        "return await self._loop.run_in_executor(\n"
        "            self._executor, _blocking_half, client\n"
        "        )",
        "return _blocking_half(client)",
    )
    assert "_blocking_half(client)" in src  # replacement applied
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["async-discipline"]), "async-discipline"
    )
    assert "blocking-on-loop" in checks


def test_async_discipline_init_exempt_from_loop_state(tmp_path):
    # __init__ constructs the loop-confined state before the loop can
    # see the object; only post-construction sync methods are flagged
    findings = run_analysis(
        _tree(tmp_path, {"mod.py": ASYNC_BAD}), rules=["async-discipline"]
    )
    assert not any(
        f.check == "loop-state-off-loop" and "__init__" in f.message
        for f in findings
    )


def test_async_discipline_suppression(tmp_path):
    src = ASYNC_BAD.replace(
        "    def reset(self):",
        "    def reset(self):  # edl-lint: disable=async-discipline"
        " -- quiesced in a test harness, loop already stopped",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["async-discipline"]), "async-discipline"
    )
    assert "loop-state-off-loop" not in checks
    assert "blocking-on-loop" in checks  # the others still fire


def test_repo_async_uds_server_declares_loop_state():
    """The real AsyncUdsServer carries the LOOP_ONLY_ATTRS declaration
    the rule keys on — the declaration and the rule can't drift apart."""
    from elasticdl_tpu.rpc.transport import AsyncUdsServer

    assert set(AsyncUdsServer.LOOP_ONLY_ATTRS) == {"_server", "_writers"}


# -- edl-verify: thread-provenance ---------------------------------------------


def test_thread_provenance_flags_race_and_role_violations(tmp_path):
    root = _tree(tmp_path, {"mod.py": THREAD_PROV_BAD})
    findings = run_analysis(root, rules=["thread-provenance"])
    checks = _checks(findings, "thread-provenance")
    assert checks == {
        "cross-thread-race",
        "role-owned-violation",
        "bad-role-declaration",
    }
    msgs = [f.message for f in findings]
    assert any("_count" in m and "no common lock" in m for m in msgs)
    assert any("_owned" in m for m in msgs)
    # the typo'd declaration is flagged, not silently trusted
    assert any("thread:Sampler._ghost" in m for m in msgs)


def test_thread_provenance_clean_under_all_rules(tmp_path):
    root = _tree(tmp_path, {"mod.py": THREAD_PROV_GOOD})
    assert run_analysis(root) == []


def test_thread_provenance_findings_carry_roles(tmp_path):
    # each finding names the inferred role set it was derived from —
    # the triage handle for deciding owner vs. lock vs. baseline
    root = _tree(tmp_path, {"mod.py": THREAD_PROV_BAD})
    findings = run_analysis(root, rules=["thread-provenance"])
    race = next(f for f in findings if f.check == "cross-thread-race")
    assert set(race.roles) == {"main", "thread:Sampler._drain"}


def test_thread_provenance_entry_held_covers_locked_helpers(tmp_path):
    # a helper whose EVERY resolved caller holds the lock inherits it
    # on entry: no false race on the helper's bare increment
    src = """
import threading


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._n = 0
        self._t = threading.Thread(target=self._work, daemon=True)

    def start(self):
        self._t.start()

    def _work(self):
        with self._lock:
            self._bump()

    def _bump(self):
        self._n += 1  # lock held by every caller

    def read(self):
        with self._lock:
            self._bump()
            return self._n
"""
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["thread-provenance"]) == []


def test_thread_provenance_suppression(tmp_path):
    src = THREAD_PROV_BAD.replace(
        "    def _drain(self):",
        "    def _drain(self):  # edl-lint: disable=thread-provenance"
        " -- drained under an external barrier in this fixture",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["thread-provenance"]), "thread-provenance"
    )
    # the race (attributed inside _drain) is suppressed; the
    # declaration findings outside the block still fire
    assert "cross-thread-race" not in checks
    assert "bad-role-declaration" in checks


def test_repo_thread_roles_cover_the_runtime():
    """Role inference discovers the repo's real thread topology — the
    loop core, the executor pool, RPC handlers, the overlap sync
    thread, the fan-in combiner, the KV mirror ring, and the recovery
    monitor. This floor is what makes the race rules mean anything."""
    ctx = load_context(PKG_ROOT)
    g = cg.CallGraph(ctx)
    roles = g.roles(tp.handler_role_seeds(ctx))
    seen = set().union(*roles.values())
    assert {
        "main",
        "loop",
        "executor",
        "rpc-handler",
        "thread:Worker._sync_local_updates.thread_main",
        "thread:CombineBuffer._combiner_loop",
        "thread:KVShardServicer._mirror_loop",
        "thread:RecoveryPlane._monitor_loop",
    } <= seen
    assert len(seen) >= 6


def test_repo_agg_forward_path_carries_combiner_role():
    """AggregatorServicer hands _forward_batch to CombineBuffer's
    constructor; ctor-callback inheritance must place it on the
    combiner thread alongside the handler-side flush path."""
    ctx = load_context(PKG_ROOT)
    g = cg.CallGraph(ctx)
    roles = g.roles(tp.handler_role_seeds(ctx))
    key = ("agg/aggregator.py", "AggregatorServicer", "_forward_batch")
    assert "thread:CombineBuffer._combiner_loop" in roles[key]


def test_repo_worker_declares_sync_error_guarded():
    """The worker publishes the overlap thread's failure through
    _sync_error under _report_lock; the SYNC_GUARDED_ATTRS declaration
    and the runtime table must not drift apart."""
    from elasticdl_tpu.worker.worker import Worker

    assert "_sync_error" in Worker.SYNC_GUARDED_ATTRS["_report_lock"]


def test_cli_json_includes_roles(tmp_path, capsys):
    root = _tree(tmp_path, {"mod.py": THREAD_PROV_BAD})
    assert (
        lint_main(
            [
                "--root", root, "--rule", "thread-provenance",
                "--no-baseline", "--format", "json",
            ]
        )
        == 1
    )
    out = json.loads(capsys.readouterr().out)
    race = next(f for f in out["new"] if f["check"] == "cross-thread-race")
    assert race["roles"] == ["main", "thread:Sampler._drain"]


# -- edl-verify: exactness-lineage ---------------------------------------------


def test_exactness_lineage_flags_all_three(tmp_path):
    root = _tree(tmp_path, {"mod.py": EXACT_BAD})
    findings = run_analysis(root, rules=["exactness-lineage"])
    checks = _checks(findings, "exactness-lineage")
    assert checks == {
        "unpinned-retry-key",
        "registration-before-apply",
        "mutating-rpc-unclassified",
    }
    msgs = [f.message for f in findings]
    assert any("push_with_retry" in m for m in msgs)
    assert any("push_delta" in m for m in msgs)
    assert any("StubMut" in m for m in msgs)


def test_exactness_lineage_clean_under_all_rules(tmp_path):
    root = _tree(tmp_path, {"mod.py": EXACT_GOOD})
    assert run_analysis(root) == []


def test_exactness_pinning_idiom_inside_loop_is_clean(tmp_path):
    # `key = key or uuid4()` INSIDE the loop still pins: the second
    # iteration reuses the first mint, so the resend replays one key
    src = EXACT_GOOD.replace(
        "    report_key = report_key or uuid.uuid4().hex\n"
        "    for attempt in range(3):",
        "    for attempt in range(3):\n"
        "        report_key = report_key or uuid.uuid4().hex",
    )
    assert "        report_key = report_key or" in src  # applied
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["exactness-lineage"]) == []


def test_exactness_order_check_is_branch_aware(tmp_path):
    # registration on the fast path, apply+register on the EXCLUSIVE
    # slow path (the ps_shard batch-apply shape): not a violation —
    # no execution runs the early reg AND the later version write
    src = """
IDEMPOTENT_METHODS = frozenset({"Push"})
DEDUP_KEYED_METHODS = frozenset({"Push"})


class S:
    def __init__(self):
        self._version = 0
        self._seen_reports = {}

    def handlers(self):
        return {"Push": self.push}

    def push(self, req):
        if req.get("fast"):
            self._seen_reports[req["report_key"]] = None
        else:
            self._apply_locked(req)
        return {}

    def _apply_locked(self, req):
        self._version += 1
        self._seen_reports[req["report_key"]] = None


def go(client):
    client.call("Push", {"report_key": "k"})
"""
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["exactness-lineage"]) == []


def test_exactness_lineage_suppression(tmp_path):
    src = EXACT_BAD.replace(
        "    def push_delta(self, req):",
        "    def push_delta(self, req):  # edl-lint: disable="
        "exactness-lineage -- apply is transactional in this fixture",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["exactness-lineage"]), "exactness-lineage"
    )
    assert "registration-before-apply" not in checks
    assert "unpinned-retry-key" in checks  # outside the block: still on


def test_repo_trace_and_agg_knobs_registered():
    """Satellite audit pin: every EDL_TRACE_*/EDL_AGG_* knob the tree
    reads is declared in ENV_REGISTRY with a real docstring — the
    env-registry family enforces the read sites, this pins the six
    knob names so a rename can't orphan a registry entry."""
    from elasticdl_tpu.common.constants import ENV_REGISTRY

    for knob in (
        "EDL_AGG_BATCH",
        "EDL_AGG_WAIT_MS",
        "EDL_AGG_UPSTREAM_TIER",
        "EDL_TRACE_SAMPLE",
        "EDL_TRACE_SEED",
        "EDL_TRACE_PROBE_SECS",
    ):
        assert knob in ENV_REGISTRY and ENV_REGISTRY[knob].strip(), knob


# -- resource-lifecycle --------------------------------------------------------


def test_resource_lifecycle_flags_all_checks(tmp_path):
    root = _tree(tmp_path, {"mod.py": RES_LIFE_BAD})
    findings = run_analysis(root, rules=["resource-lifecycle"])
    checks = _checks(findings, "resource-lifecycle")
    assert checks == {
        "leak-on-raise-path",
        "start-without-join-or-daemon",
        "acquire-without-finally",
        "unreleased-escape",
    }
    msgs = [f.message for f in findings]
    assert any("seg" in m and "publish" in m for m in msgs)
    assert any("PoolOwner" in m and "_pool" in m for m in msgs)


def test_resource_lifecycle_clean_under_all_rules(tmp_path):
    root = _tree(tmp_path, {"mod.py": RES_LIFE_GOOD})
    assert run_analysis(root) == []


def test_resource_lifecycle_findings_carry_release_chain(tmp_path):
    # the interprocedural hand-off is IN the finding: lend -> _checkin
    # -> self._pool is the triage trail for where the release belongs
    root = _tree(tmp_path, {"mod.py": RES_LIFE_BAD})
    findings = run_analysis(root, rules=["resource-lifecycle"])
    esc = next(f for f in findings if f.check == "unreleased-escape")
    assert esc.chain == ("PoolOwner.lend", "PoolOwner._checkin", "self._pool")


def test_resource_lifecycle_factory_return_propagates(tmp_path):
    # a factory that RETURNS the resource transfers ownership to its
    # caller — the caller inherits the release obligation
    src = """import socket


def make_conn(host):
    conn = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    try:
        conn.connect(host)
    except OSError:
        conn.close()
        raise
    return conn


def use(host, payload):
    conn = make_conn(host)
    conn.sendall(payload)
"""
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["resource-lifecycle"])
    assert [(f.check, f.chain) for f in findings] == [
        ("leak-on-raise-path", ("use", "conn"))
    ]


def test_resource_lifecycle_acquire_then_try_finally_is_clean(tmp_path):
    # the manual acquire immediately followed by try/finally release is
    # THE sanctioned non-`with` shape; only the bare form is flagged
    src = """def locked(lock):
    lock.acquire()
    try:
        return 1
    finally:
        lock.release()
"""
    root = _tree(tmp_path, {"mod.py": src})
    assert run_analysis(root, rules=["resource-lifecycle"]) == []


def test_resource_lifecycle_suppression(tmp_path):
    src = RES_LIFE_BAD.replace(
        "def leaks_segment_on_raise(name, payload):",
        "def leaks_segment_on_raise(name, payload):"
        "  # edl-lint: disable=resource-lifecycle -- fixture keeps the"
        " segment alive for a sibling process",
    )
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["resource-lifecycle"])
    lines = {(f.check, f.message.split()[0]) for f in findings}
    assert ("leak-on-raise-path", "leaks_segment_on_raise") not in lines
    assert ("leak-on-raise-path", "never_released") in lines


def test_repo_close_like_release_chains():
    """The live tree's teardown chains the burn-down relies on, pinned
    as negatives: ServerDispatcher drains its executor, StandbyMaster's
    adoption-abort path joins the watch thread and stops the adopted
    server, and AsyncUdsServer releases its asyncio server through the
    _close_async hop — if a refactor breaks any of these hand-offs the
    chain disappears and unreleased-escape fires on the tree."""
    ctx = load_context(PKG_ROOT)
    g = cg.CallGraph(ctx)
    an = rl.Analysis(ctx, g)
    an._summaries_fixpoint()
    dispatcher = ("rpc/transport.py", "ServerDispatcher")
    assert an.release_chain(dispatcher, "_executor") == (
        "ServerDispatcher.close", "self._executor",
    )
    standby = ("master/migration.py", "StandbyMaster")
    assert an.release_chain(standby, "_watch_thread") == (
        "StandbyMaster.stop", "self._watch_thread",
    )
    assert an.release_chain(standby, "server") == (
        "StandbyMaster.stop", "self.server",
    )
    auds = ("rpc/transport.py", "AsyncUdsServer")
    assert an.release_chain(auds, "_server") == (
        "AsyncUdsServer.close", "AsyncUdsServer._close_async", "self._server",
    )


# -- shutdown-order ------------------------------------------------------------


def test_shutdown_order_flags_all_checks(tmp_path):
    root = _tree(tmp_path, {"mod.py": SHUT_ORDER_BAD})
    findings = run_analysis(root, rules=["shutdown-order"])
    checks = _checks(findings, "shutdown-order")
    assert checks == {
        "join-under-lock",
        "close-order-inversion",
        "double-close-unsafe",
    }
    msgs = [f.message for f in findings]
    assert any("_lock" in m and "join" in m for m in msgs)
    assert any("_conn" in m and "_pump" in m for m in msgs)


def test_shutdown_order_clean_under_all_rules(tmp_path):
    root = _tree(tmp_path, {"mod.py": SHUT_ORDER_GOOD})
    assert run_analysis(root) == []


def test_shutdown_order_join_under_with_block_too(tmp_path):
    # the `with` form of the same deadlock — the manual-acquire form is
    # the fixture's; both must land on the join line
    src = SHUT_ORDER_BAD.replace(
        "        self._lock.acquire()\n"
        "        try:\n"
        "            self._t.join()\n"
        "        finally:\n"
        "            self._lock.release()",
        "        with self._lock:\n"
        "            self._t.join()",
    )
    assert "with self._lock" in src  # replacement applied
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["shutdown-order"])
    assert "join-under-lock" in _checks(findings, "shutdown-order")


def test_shutdown_order_wake_idiom_is_load_bearing(tmp_path):
    # WakesTheReader is exempt ONLY because the thread sits in a
    # blocking accept; turn the read into a write and the same
    # close-before-join order becomes an inversion
    src = SHUT_ORDER_GOOD.replace(
        "self._sock.accept()", "self._sock.sendall(b'x')"
    )
    assert "sendall" in src  # replacement applied
    root = _tree(tmp_path, {"mod.py": src})
    findings = run_analysis(root, rules=["shutdown-order"])
    assert _checks(findings, "shutdown-order") == {"close-order-inversion"}


def test_shutdown_order_findings_carry_chain(tmp_path):
    root = _tree(tmp_path, {"mod.py": SHUT_ORDER_BAD})
    findings = run_analysis(root, rules=["shutdown-order"])
    inv = next(f for f in findings if f.check == "close-order-inversion")
    assert inv.chain[0] == "ClosesBeforeDrain.close"
    assert "self._conn" in inv.chain and "self._pump" in inv.chain


def test_shutdown_order_suppression(tmp_path):
    src = SHUT_ORDER_BAD.replace(
        "    def stop(self):",
        "    def stop(self):  # edl-lint: disable=shutdown-order"
        " -- the loop provably exits before stop in this fixture",
    )
    root = _tree(tmp_path, {"mod.py": src})
    checks = _checks(
        run_analysis(root, rules=["shutdown-order"]), "shutdown-order"
    )
    assert "join-under-lock" not in checks
    assert "close-order-inversion" in checks  # other class: still on


def test_cli_json_includes_chain(tmp_path, capsys):
    root = _tree(tmp_path, {"mod.py": RES_LIFE_BAD})
    assert (
        lint_main(
            [
                "--root", root, "--rule", "resource-lifecycle",
                "--no-baseline", "--format", "json",
            ]
        )
        == 1
    )
    out = json.loads(capsys.readouterr().out)
    esc = next(f for f in out["new"] if f["check"] == "unreleased-escape")
    assert esc["chain"] == ["PoolOwner.lend", "PoolOwner._checkin", "self._pool"]


def test_cli_stats_flag(tmp_path, capsys):
    root = _tree(tmp_path, {"mod.py": RES_LIFE_BAD})
    assert lint_main(["--root", root, "--no-baseline", "--stats"]) == 1
    out = capsys.readouterr().out
    assert "per-family counts" in out
    # every selected family gets a row, firing or not
    for family in ("resource-lifecycle", "shutdown-order", "lock-discipline"):
        assert family in out
    # json always carries the same table
    assert (
        lint_main(["--root", root, "--no-baseline", "--format", "json"]) == 1
    )
    stats = json.loads(capsys.readouterr().out)["stats"]
    assert stats["resource-lifecycle"]["new"] == 5
    assert stats["shutdown-order"]["new"] == 0


# -- edl-verify: the call-graph engine -----------------------------------------


def test_callgraph_resolution_and_lock_tracking(tmp_path):
    src = """
import threading
import time


def helper():
    time.sleep(0.1)


class C:
    def __init__(self):
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)

    def leaf(self):
        with self._lock:
            return 1

    def top(self):
        with self._cv:
            helper()
        return self.leaf()
"""
    g = cg.CallGraph(load_context(_tree(tmp_path, {"mod.py": src})))
    top = ("mod.py", "C", "top")
    leaf = ("mod.py", "C", "leaf")
    callees = {e.callee for e in g.edges[top]}
    assert ("mod.py", None, "helper") in callees  # same-module call
    assert leaf in callees  # self-method call
    lock = ("mod.py::C", "_lock")
    # Condition(self._lock) aliases to the lock it wraps
    assert {a.lock for a in g.acquires[top]} == {lock}
    assert g.transitive_acquires(top) == {lock}
    assert g.may_block(top)  # via helper's time.sleep
    assert g.blocking_chain(("mod.py", None, "helper")) == [
        "helper", "time.sleep"
    ]
    assert g.lock_name(lock) == "C._lock"


def test_callgraph_unresolvable_calls_make_no_edges(tmp_path):
    src = """
def f(obj):
    obj.anything()
    unknown_name()


def unknown_name():
    return 1
"""
    g = cg.CallGraph(load_context(_tree(tmp_path, {"mod.py": src})))
    callees = {e.callee for e in g.edges.get(("mod.py", None, "f"), [])}
    # obj.anything() is unresolvable -> dropped; the bare name resolves
    assert callees == {("mod.py", None, "unknown_name")}


def test_parse_error_is_a_finding(tmp_path):
    root = _tree(tmp_path, {"broken.py": "def f(:\n"})
    checks = {(f.rule, f.check) for f in run_analysis(root)}
    assert ("lint", "parse-error") in checks


def test_cli_exit_codes_both_directions(tmp_path):
    bad = _tree(tmp_path / "bad", {"mod.py": LOCK_BAD})
    good = _tree(tmp_path / "good", {"mod.py": LOCK_GOOD})
    assert lint_main(["--root", bad, "--no-baseline"]) == 1
    assert lint_main(["--root", good, "--no-baseline"]) == 0


@pytest.mark.parametrize("rule", RULE_FAMILIES)
def test_cli_rule_selection(tmp_path, rule):
    sources = {
        "rpc-conformance": RPC_BAD_NO_HANDLER,
        "lock-discipline": LOCK_BAD,
        "jit-purity": JIT_BAD,
        "env-registry": ENV_BAD,
        "metric-registry": METRIC_BAD,
        "fencing-conformance": FENCING_BAD,
        "lock-order": LOCK_ORDER_BAD,
        "abort-discipline": ABORT_BAD,
        "async-discipline": ASYNC_BAD,
        "thread-provenance": THREAD_PROV_BAD,
        "exactness-lineage": EXACT_BAD,
        "resource-lifecycle": RES_LIFE_BAD,
        "shutdown-order": SHUT_ORDER_BAD,
    }
    root = _tree(tmp_path, {"mod.py": sources[rule]})
    assert lint_main(["--root", root, "--rule", rule, "--no-baseline"]) == 1
    others = [r for r in RULE_FAMILIES if r != rule]
    args = ["--root", root, "--no-baseline"]
    for r in others:
        args += ["--rule", r]
    # ENV_BAD embeds no other family's violation; same for the rest
    assert lint_main(args) == 0


def test_baseline_workflow(tmp_path, capsys):
    root = _tree(tmp_path, {"mod.py": LOCK_BAD})
    baseline = str(tmp_path / "baseline.json")
    # accept the current findings, then the run is clean
    assert lint_main(["--root", root, "--write-baseline", "--baseline", baseline]) == 0
    assert lint_main(["--root", root, "--baseline", baseline]) == 0
    # a NEW finding is not covered by the baseline
    (tmp_path / "mod2.py").write_text(LOCK_BAD)
    assert lint_main(["--root", root, "--baseline", baseline]) == 1
    # fixing everything leaves stale entries: ok, unless --strict-baseline
    (tmp_path / "mod.py").write_text(LOCK_GOOD)
    (tmp_path / "mod2.py").write_text(LOCK_GOOD)
    assert lint_main(["--root", root, "--baseline", baseline]) == 0
    assert (
        lint_main(["--root", root, "--baseline", baseline, "--strict-baseline"])
        == 1
    )
    capsys.readouterr()


def test_cli_json_format(tmp_path, capsys):
    root = _tree(tmp_path, {"mod.py": LOCK_BAD})
    assert lint_main(["--root", root, "--no-baseline", "--format", "json"]) == 1
    out = json.loads(capsys.readouterr().out)
    assert out["new"] and out["baselined"] == 0
    assert {"rule", "check", "path", "line", "message"} <= set(out["new"][0])


def test_baseline_key_is_line_free(tmp_path):
    root = _tree(tmp_path, {"mod.py": LOCK_BAD})
    baseline = str(tmp_path / "baseline.json")
    assert lint_main(["--root", root, "--write-baseline", "--baseline", baseline]) == 0
    # shifting the findings by a line must not invalidate the baseline
    (tmp_path / "mod.py").write_text("# a leading comment\n" + LOCK_BAD)
    assert lint_main(["--root", root, "--baseline", baseline]) == 0


# -- the live repo ------------------------------------------------------------


def test_repo_is_lint_clean():
    """The checked-in tree passes with the checked-in baseline; this is
    the same invocation the CI analysis job runs."""
    res = subprocess.run(
        [sys.executable, "-m", "elasticdl_tpu.analysis"],
        capture_output=True,
        text=True,
        cwd=REPO_ROOT,
    )
    assert res.returncode == 0, res.stdout + res.stderr


def test_repo_every_called_method_has_handler():
    ctx = load_context(PKG_ROOT)
    handlers = rc._collect_handlers(ctx)
    called = {s.method for s in rc._collect_call_sites(ctx)}
    assert called, "call-site collector found nothing — collector broken"
    assert called <= set(handlers), f"unhandled: {sorted(called - set(handlers))}"


def test_repo_policy_sets_match_ast_view():
    """The AST-collected retry classification IS rpc/policy.py's —
    proves the lint checks the real policy, not a stale copy."""
    from elasticdl_tpu.rpc.policy import DEDUP_KEYED_METHODS, IDEMPOTENT_METHODS

    policy = rc._policy_sets(load_context(PKG_ROOT))
    assert policy["IDEMPOTENT_METHODS"][2] == set(IDEMPOTENT_METHODS)
    assert policy["DEDUP_KEYED_METHODS"][2] == set(DEDUP_KEYED_METHODS)
    assert set(DEDUP_KEYED_METHODS) <= set(IDEMPOTENT_METHODS)


def test_repo_schemas_cover_handlers_exactly():
    from elasticdl_tpu.common.messages import WIRE_SCHEMAS

    ctx = load_context(PKG_ROOT)
    handlers = rc._collect_handlers(ctx)
    assert set(handlers) == set(WIRE_SCHEMAS)


def test_repo_callgraph_sees_the_tree():
    """The engine resolves the live tree at scale: hundreds of
    functions, the worker's preamble edges, the Condition alias in the
    recovery plane, and the worker's report lock."""
    g = cg.CallGraph(load_context(PKG_ROOT))
    assert len(g.functions) > 500
    key = ("worker/worker.py", "Worker", "_ensure_local_ready")
    callees = {e.callee[2] for e in g.edges[key]}
    assert {"pull_model", "_join_sync"} <= callees
    assert ("worker/worker.py::Worker", "_report_lock") in g.lock_kinds
    # Condition(self._lock) in RecoveryPlane aliases to _lock: no
    # phantom second lock, and its acquires resolve to the real one
    assert ("master/recovery.py::RecoveryPlane", "_cv") not in g.lock_kinds
    offer = ("master/recovery.py", "RecoveryPlane", "offer_upload")
    assert {a.lock for a in g.acquires[offer]} == {
        ("master/recovery.py::RecoveryPlane", "_lock")
    }


def test_repo_unfenced_declaration_matches_runtime():
    """The AST-extracted UNFENCED_HANDLERS table IS the runtime one,
    and only names methods the servicer actually registers — the same
    cross-check style as the policy-set test above."""
    from elasticdl_tpu.master.kv_shard import KVShardServicer

    ctx = load_context(PKG_ROOT)
    tree = ctx.files["master/kv_shard.py"].tree
    cls = next(
        n
        for n in ast.walk(tree)
        if isinstance(n, ast.ClassDef) and n.name == "KVShardServicer"
    )
    declared, _line = fc._declared_unfenced(cls)
    assert declared == set(KVShardServicer.UNFENCED_HANDLERS)
    registered = {
        h.method
        for hs in rc._collect_handlers(ctx).values()
        for h in hs
        if h.cls is not None and h.cls.name == "KVShardServicer"
    }
    assert declared < registered  # declared, registered, and not all


def test_repo_handler_reachability_covers_helpers():
    """Abort-discipline's walk reaches helpers several frames below a
    registered handler (KVUpdate -> kv_update -> _enqueue_mirror)."""
    ctx = load_context(PKG_ROOT)
    g = cg.CallGraph(ctx)
    roots = []
    for h in (h for hs in rc._collect_handlers(ctx).values() for h in hs):
        if h.func is None:
            continue
        key = (h.path, h.cls.name if h.cls else None, h.func.name)
        if key in g.functions:
            roots.append((key, h.method))
    assert len(roots) > 20
    reach = ad._handler_reachable(g, roots)
    helper = ("master/kv_shard.py", "KVShardServicer", "_enqueue_mirror")
    assert reach[helper] == "KVUpdate"


# -- edl-verify CLI surface ----------------------------------------------------


def test_cli_list_rules(capsys):
    assert lint_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULE_FAMILIES:
        assert rule in out


def test_list_rules_families_are_documented():
    # the golden gate: a family cannot ship without a
    # docs/static_analysis.md section naming it
    with open(
        os.path.join(REPO_ROOT, "docs", "static_analysis.md"),
        encoding="utf-8",
    ) as f:
        doc = f.read()
    for rule in RULE_FAMILIES:
        assert f"`{rule}`" in doc, f"{rule} missing from docs"


def test_cli_github_format(tmp_path, capsys):
    root = _tree(tmp_path, {"mod.py": LOCK_ORDER_BAD})
    rc_code = lint_main(
        ["--root", root, "--no-baseline", "--format", "github"]
    )
    assert rc_code == 1
    lines = [
        ln for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("::error ")
    ]
    assert lines
    assert any("title=lock-order/lock-cycle" in ln for ln in lines)
    assert all("file=" in ln and ",line=" in ln for ln in lines)


def test_baseline_verify_families_require_comment(tmp_path):
    path = str(tmp_path / "baseline.json")
    key = "lock-order|lock-cycle|mod.py|some cycle"
    with open(path, "w") as f:
        json.dump({"findings": [key]}, f)
    with pytest.raises(ValueError, match="commented form"):
        load_baseline(path)
    with open(path, "w") as f:
        json.dump({"findings": [{"key": key, "comment": "  "}]}, f)
    with pytest.raises(ValueError, match="empty comment"):
        load_baseline(path)
    with open(path, "w") as f:
        json.dump(
            {"findings": [{"key": key, "comment": "reviewed: benign"}]}, f
        )
    assert load_baseline(path) == {key: 1}


def test_write_baseline_emits_commented_verify_entries(tmp_path):
    root = _tree(tmp_path, {"mod.py": LOCK_ORDER_BAD})
    baseline = str(tmp_path / "baseline.json")
    assert (
        lint_main(["--root", root, "--write-baseline", "--baseline", baseline])
        == 0
    )
    with open(baseline) as f:
        entries = json.load(f)["findings"]
    assert entries and all(isinstance(e, dict) for e in entries)
    assert all(e["comment"] for e in entries)  # placeholder, but present
    assert lint_main(["--root", root, "--baseline", baseline]) == 0
