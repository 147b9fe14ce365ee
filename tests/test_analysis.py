"""Tier-1 gate for the static-analysis suite (orion_tpu/analysis/).

Every Tier A lint rule is exercised with a positive (seeded violation) and a
negative (clean idiom) fixture; every Tier B jaxpr contract with a deliberate
toy violation and a clean counterpart — assertions are on rule ids, never
message text. The repo itself must come out clean: the CLI exiting 0 on the
tree at merge is an acceptance criterion, so `test_repo_*_clean` failing
means a real regression (or a finding that needs an in-line noqa / baseline
entry with a rationale).
"""

import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

from orion_tpu.analysis import jaxpr_audit
from orion_tpu.analysis.findings import (
    BaselineEntry,
    apply_baseline,
    load_baseline,
)
from orion_tpu.analysis.lint import lint_source
from orion_tpu.analysis.rules import ALL_RULES

pytestmark = pytest.mark.analysis


def rule_ids(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# Tier A: one positive + one negative fixture per rule
# ---------------------------------------------------------------------------

# (rule-id, virtual path, bad source, clean source)
RULE_CASES = [
    (
        "jit-debug",
        "orion_tpu/dummy.py",
        """
import jax

@jax.jit
def f(x):
    print("tracing", x)
    return x
""",
        """
import jax

@jax.jit
def f(x):
    return x

def host_log(x):
    print("host side is fine", x)
""",
    ),
    (
        "jit-debug",
        "orion_tpu/dummy.py",
        """
import jax

@jax.jit
def f(x):
    jax.debug.print("x={}", x)
    return x
""",
        """
import jax

def f(x):
    jax.debug.print("not jitted, allowed", x)
    return x
""",
    ),
    (
        "tracer-host",
        "orion_tpu/dummy.py",
        """
import jax
import numpy as np

@jax.jit
def f(x):
    a = x.item()
    b = float(x)
    c = np.asarray(x)
    return a + b + c.sum()
""",
        """
import jax
import jax.numpy as jnp

@jax.jit
def f(x):
    return x.astype(jnp.float32) + float(1.5)

def host(x):
    return float(x)  # untraced host code may concretize
""",
    ),
    (
        "static-hashable",
        "orion_tpu/dummy.py",
        """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1,))
def f(x, opts: list):
    return x

@partial(jax.jit, static_argnames=("cfg",))
def g(x, cfg={}):
    return x
""",
        """
import jax
from functools import partial

@partial(jax.jit, static_argnums=(1, 2))
def f(x, n: int, name: str = "a"):
    return x
""",
    ),
    (
        "loop-accum",
        "orion_tpu/generate.py",  # hot path
        """
import jax.numpy as jnp

def decode_all(xs):
    out = jnp.zeros((0, 4))
    total = 0.0
    for x in xs:
        out = jnp.concatenate([out, x])
        total += jnp.sum(x)
    return out, total
""",
        """
import jax
import jax.numpy as jnp

def decode_all(xs):
    def body(carry, x):
        return carry + jnp.sum(x), x
    total, out = jax.lax.scan(body, 0.0, xs)
    return out, total
""",
    ),
    (
        "float64-literal",
        "orion_tpu/dummy.py",
        """
import jax.numpy as jnp

def f(x):
    return x.astype(jnp.float64) + jnp.asarray(1.0, dtype="float64")
""",
        """
import jax.numpy as jnp

def f(x):
    return x.astype(jnp.float32)
""",
    ),
    (
        "mutable-default",
        "orion_tpu/dummy.py",
        """
def f(x, acc=[], table={}):
    return x
""",
        """
def f(x, acc=None, table=()):
    return x
""",
    ),
    (
        "bare-except",
        "orion_tpu/dummy.py",
        """
def f(x):
    try:
        return x
    except:
        return None
""",
        """
def f(x):
    try:
        return x
    except ValueError:
        return None
""",
    ),
    (
        "unbounded-wait",
        "orion_tpu/dummy.py",
        """
import queue
import threading

_q = queue.Queue()

def consume(worker: threading.Thread):
    item = _q.get()
    also = _q.get(block=True)
    worker.join()
    return item, also
""",
        """
import queue
import threading

_q = queue.Queue()

def consume(worker: threading.Thread, opts: dict):
    item = _q.get(timeout=5.0)
    worker.join(timeout=2.0)
    name = opts.get("name")        # dict.get needs a key: not a wait
    path = "/".join(["a", "b"])    # str.join needs operands: not a wait
    fast = _q.get_nowait()
    return item, name, path, fast
""",
    ),
    (
        "signal-unsafe-handler",
        "orion_tpu/dummy.py",
        """
import signal

_STOP = False

def _handle(signum, frame):
    global _STOP
    _STOP = True
    print("preempted")
    with open("/tmp/preempt.log", "a") as f:
        f.write("caught")
    _save_everything()

def _save_everything():
    ckpt.save(state)

signal.signal(signal.SIGTERM, _handle)
""",
        """
import os
import signal

_STOP = False

def _handle(signum, frame):
    global _STOP
    _STOP = True
    os.write(2, b"[preempt] stopping at the next step boundary\\n")

signal.signal(signal.SIGTERM, _handle)

def host_side(ckpt, state, lock):
    print("not a handler: io is fine here")
    with lock:
        ckpt.save(state)
""",
    ),
    (
        "pallas-chunk-guard",
        "orion_tpu/ops/pallas/dummy.py",
        """
import jax.experimental.pallas as pl

def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def entry(x, chunk):
    return pl.pallas_call(_kernel, out_shape=x)(x)
""",
        """
import jax.experimental.pallas as pl

def _kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]

def entry(x, chunk):
    assert x.shape[-2] % chunk == 0, (x.shape, chunk)
    return pl.pallas_call(_kernel, out_shape=x)(x)

def padded_entry(x, chunk):
    import jax.numpy as jnp
    rem = (-x.shape[-2]) % chunk
    x = jnp.pad(x, ((0, 0), (0, rem), (0, 0)))
    return pl.pallas_call(_kernel, out_shape=x)(x)
""",
    ),
    (
        "decode-host-sync",
        "orion_tpu/serving/dummy.py",
        """
import numpy as np

def serve_loop(chunks):
    outs = []
    while chunks:
        c = chunks.pop()
        c.block_until_ready()
        outs.append(np.asarray(c))
        lat = float(c[0])
    return outs
""",
        """
import numpy as np

def _probe_finite(state):
    return float(state.sum())  # designated probe: the sanctioned sync

def serve_loop(chunks):
    outs = []
    for c in chunks:
        if not _probe_finite(c):
            break
        outs.append(c)
    return np.asarray(outs)  # one sync AFTER the loop
""",
    ),
    (
        "non-atomic-persist",
        "orion_tpu/serving/dummy.py",
        """
import json

def publish_state(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)
""",
        """
import json
import os

def publish_state(path, payload):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f)
    os.replace(tmp, path)  # atomic publish

def read_state(path):
    with open(path) as f:
        return json.load(f)

def append_log(path, line):
    with open(path, "a") as f:  # append-only logs are prefix-valid
        f.write(line)
""",
    ),
    (
        "obs-device-sync",
        "orion_tpu/obs/dummy.py",
        """
import jax
import jax.numpy as jnp
import numpy as np

def scrape(state):
    v = float(state.sum())
    state.block_until_ready()
    return np.asarray(state), v, int(jnp.max(state))
""",
        """
import json
import threading

def scrape(registry):
    with registry._lock:
        return json.dumps(dict(registry._counters))

def record(ring, kind, value):
    ring.append((kind, value))  # host numbers in, host numbers out
""",
    ),
    (
        "obs-device-sync",
        "orion_tpu/serving/obs_hooks_dummy.py",
        """
def slot_gauge(engine):
    return float(engine.state.sum())  # device sync inside a gauge fn

def wire(registry, engine):
    registry.gauge_fn("slots_active", slot_gauge)
""",
        """
def slot_gauge(engine):
    return engine.active_count  # the host mirror, already an int

def wire(registry, engine):
    registry.gauge_fn("slots_active", slot_gauge)

def host_eval(x):
    return float(x)  # NOT registered as a hook: plain host code is fine
""",
    ),
    (
        "non-atomic-persist",
        "orion_tpu/resilience/dummy.py",
        """
def checkpoint_meta(path, blob):
    f = open(path, mode="wb")
    f.write(blob)
    f.close()
""",
        """
import os

def checkpoint_meta(path, blob):
    with open(path + ".tmp", mode="wb") as f:
        f.write(blob)
    os.rename(path + ".tmp", path)
""",
    ),
    (
        "raw-store-io",
        "orion_tpu/serving/session_store.py",
        """
import os

def newest_generation(d):
    return sorted(os.listdir(d))[-1]  # raw syscall: no breaker gate
""",
        """
import os

def _io_listdir(d):
    # breaker-gated helper: blocked() checked before the syscall
    return os.listdir(d)

def newest_generation(d):
    return sorted(_io_listdir(d))[-1]
""",
    ),
]


def test_raw_store_io_scoped_to_store_modules():
    """The same raw listdir in any OTHER serving module is not a finding —
    the rule encodes the _io_* discipline of the two shared-storage
    clients, whose syscalls must all pass the circuit-breaker gate."""
    src = """
import os

def scan(d):
    return os.listdir(d)
"""
    assert "raw-store-io" in rule_ids(
        lint_source(src, path="orion_tpu/serving/prefix_store.py")
    )
    assert "raw-store-io" not in rule_ids(
        lint_source(src, path="orion_tpu/serving/server.py")
    )
    assert "raw-store-io" not in rule_ids(
        lint_source(src, path="tests/test_dummy.py")
    )


def test_non_atomic_persist_scoped_to_persistence_subtrees():
    """The same in-place write OUTSIDE serving//resilience//training (a
    bench script, an exp harness) is not a finding — the rule encodes the
    durability contract of the persistence layers, not a global style."""
    src = """
import json

def dump(path, payload):
    with open(path, "w") as f:
        json.dump(payload, f)
"""
    assert "non-atomic-persist" in rule_ids(
        lint_source(src, path="orion_tpu/training/dummy.py")
    )
    assert "non-atomic-persist" not in rule_ids(
        lint_source(src, path="orion_tpu/analysis/dummy.py")
    )
    assert "non-atomic-persist" not in rule_ids(
        lint_source(src, path="tests/test_dummy.py")
    )


@pytest.mark.parametrize(
    "rule,path,bad,clean",
    RULE_CASES,
    ids=[f"{c[0]}-{i}" for i, c in enumerate(RULE_CASES)],
)
def test_rule_positive_and_negative(rule, path, bad, clean):
    assert rule in rule_ids(lint_source(bad, path=path))
    assert rule not in rule_ids(lint_source(clean, path=path))


def test_every_registered_rule_has_a_fixture():
    covered = {c[0] for c in RULE_CASES}
    assert covered == set(ALL_RULES), (
        "every rule in the registry needs a positive+negative fixture here"
    )
    assert len(ALL_RULES) >= 8


def test_unbounded_wait_fleet_scope_widens_to_wait_and_recv():
    """In orion_tpu/fleet/ the peer of a wait is a child OS process, so
    no-timeout ``.wait()``/``.recv()`` are findings there — and only
    there (elsewhere those names are too ambiguous to flag)."""
    bad = """
def reap(proc, conn, ev):
    proc.wait()
    msg = conn.recv()
    ev.wait()
    return msg
"""
    clean = """
def reap(proc, conn, ev):
    proc.wait(timeout=10.0)
    conn.settimeout(2.0)
    msg = conn.recv(4096)     # sized read on a timeout'd socket
    ev.wait(timeout=1.0)
    return msg
"""
    assert "unbounded-wait" in rule_ids(
        lint_source(bad, path="orion_tpu/fleet/replica_dummy.py")
    )
    assert "unbounded-wait" not in rule_ids(
        lint_source(clean, path="orion_tpu/fleet/replica_dummy.py")
    )
    # outside fleet/ the widened methods stay un-flagged...
    assert "unbounded-wait" not in rule_ids(
        lint_source(bad, path="orion_tpu/training/dummy.py")
    )
    # ...while the classic get/join findings still fire in fleet/ too
    classic = """
import queue

_q = queue.Queue()

def pump(worker):
    worker.join()
    return _q.get()
"""
    assert "unbounded-wait" in rule_ids(
        lint_source(classic, path="orion_tpu/fleet/router_dummy.py")
    )


def test_unbounded_wait_obs_scope_widens_to_acquire_and_wait():
    """In orion_tpu/obs/ scrape-handler threads read state the scheduler
    writes: no-timeout ``.acquire()``/``.wait()``/``.recv()`` are
    findings there (ISSUE 10) — a hung scheduler must surface as a
    failed scrape, never a hung /metrics endpoint. Bounded and
    non-blocking forms pass; outside obs/ and fleet/ the widened names
    stay un-flagged. Which locks the ``.acquire()`` widening covers
    comes from the Tier D declaration (serving/locks.py
    ``obs_lock_attrs()``, ISSUE 16) — fixtures name the declared
    ``_lock`` attribute."""
    bad = """
class Reg:
    def scrape(self, ev, conn):
        self._lock.acquire()
        ev.wait()
        return conn.recv()
"""
    clean = """
class Reg:
    def scrape(self, ev, conn):
        if not self._lock.acquire(timeout=1.0):
            return None
        got = self._lock.acquire(blocking=False)
        ev.wait(timeout=0.5)
        conn.settimeout(2.0)
        return conn.recv(4096), got
"""
    assert "unbounded-wait" in rule_ids(
        lint_source(bad, path="orion_tpu/obs/http_dummy.py")
    )
    assert "unbounded-wait" not in rule_ids(
        lint_source(clean, path="orion_tpu/obs/http_dummy.py")
    )
    # outside obs/ (and fleet/) acquire/wait/recv stay un-flagged...
    assert "unbounded-wait" not in rule_ids(
        lint_source(bad, path="orion_tpu/training/dummy.py")
    )
    # ...and the classic get/join findings still fire inside obs/
    classic = """
import queue

_q = queue.Queue()

def pump(worker):
    worker.join()
    return _q.get()
"""
    assert "unbounded-wait" in rule_ids(
        lint_source(classic, path="orion_tpu/obs/metrics_dummy.py")
    )


def test_unbounded_wait_obs_acquire_scope_is_the_lock_declaration():
    """The two directions the rule docstring promises but ISSUE 16 found
    untested: (a) ``with lock:`` in obs is NOT a finding — the bounded
    snapshot-hold idiom is the approved shape, only the bare blocking
    ``acquire()`` call is in scope; (b) the declaration is the source of
    truth — an ``.acquire()`` on a receiver that is not a declared obs
    lock (serving/locks.py) is some other object's protocol and stays
    un-flagged, while the declared ``_default_lock`` module-global is
    covered without this rule naming it anywhere."""
    with_stmt = """
class Reg:
    def scrape(self):
        with self._lock:
            return dict(self._counters)
"""
    assert "unbounded-wait" not in rule_ids(
        lint_source(with_stmt, path="orion_tpu/obs/metrics_dummy.py")
    )
    undeclared = """
def scrape(sem):
    sem.acquire()
    return sem
"""
    assert "unbounded-wait" not in rule_ids(
        lint_source(undeclared, path="orion_tpu/obs/http_dummy.py")
    )
    declared_global = """
def configure(rec):
    _default_lock.acquire()
    return rec
"""
    assert "unbounded-wait" in rule_ids(
        lint_source(declared_global, path="orion_tpu/obs/flight_dummy.py")
    )


def test_obs_device_sync_covers_http_provider_keywords():
    """Functions registered as obs/http.py endpoint providers
    (metrics_fn/health_fn/statusz_fn/slo_fn) run on scrape-handler
    threads: a device sync inside one stalls the serving process once
    per scrape — ISSUE 10 puts them in the banned-sync scope. The same
    body unregistered stays un-flagged."""
    bad = """
def healthz_payload(server):
    return {"loss": float(server.state.sum())}  # syncs per scrape

def wire(http_cls, server):
    return http_cls(port=0, health_fn=healthz_payload)
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(bad, path="orion_tpu/serving/dummy.py")
    )
    clean = """
def healthz_payload(server):
    return {"state": server.health_value, "code": 200}

def wire(http_cls, server):
    return http_cls(port=0, health_fn=healthz_payload)

def host_eval(x):
    return float(x)  # NOT registered: plain host code is fine
"""
    assert "obs-device-sync" not in rule_ids(
        lint_source(clean, path="orion_tpu/serving/dummy.py")
    )
    # lambdas registered as providers are claimed too
    lam = """
def wire(http_cls, engine):
    return http_cls(port=0, slo_fn=lambda: float(engine.state.sum()))
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(lam, path="orion_tpu/fleet/dummy.py")
    )


def test_unbounded_wait_exempts_tests():
    src = """
import queue

_q = queue.Queue()

def poll(worker):
    worker.join()
    return _q.get()
"""
    # tests may legitimately block on a result
    assert "unbounded-wait" not in rule_ids(
        lint_source(src, path="tests/test_dummy.py")
    )
    assert "unbounded-wait" in rule_ids(
        lint_source(src, path="orion_tpu/training/dummy.py")
    )


def test_obs_device_sync_covers_hook_registration_forms():
    """Every way a callable enters the telemetry spine — hook keywords
    (on_event/on_transition/observer/...), ``add_observer``, and
    ``pending.on_done = fn`` assignment — marks that function's body as
    a hot-path hook: a device sync inside is a finding; the same code
    unregistered is not."""
    kw = """
def on_health(old, new, reason):
    latency = float(new.state.sum())  # syncs on every transition
    return latency

def wire(machine):
    machine.configure(on_transition=on_health)
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(kw, path="orion_tpu/serving/dummy.py")
    )
    assign = """
def close_span(p):
    p.result.tokens.block_until_ready()

def attach(pending):
    pending.on_done = close_span
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(assign, path="orion_tpu/fleet/dummy.py")
    )
    observer = """
def on_fault(site, step):
    import jax
    jax.device_get(step)

def wire(ring):
    ring.add_observer(on_fault)
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(observer, path="orion_tpu/resilience/dummy.py")
    )
    # the identical body NOT registered anywhere stays un-flagged
    free = """
def on_health(old, new, reason):
    latency = float(new.state.sum())
    return latency
"""
    assert "obs-device-sync" not in rule_ids(
        lint_source(free, path="orion_tpu/serving/dummy.py")
    )
    # and tests may do whatever they like
    assert "obs-device-sync" not in rule_ids(
        lint_source(kw, path="tests/test_dummy.py")
    )


def test_obs_device_sync_covers_cost_surfaces():
    """ISSUE 15: the cost/capacity hook surfaces are banned-sync scope —
    the ``costz_fn``/``profilez_fn`` endpoint providers, ``cost_fn``/
    ``capacity_fn`` callbacks, and any ``*_cost``-named function passed
    as a callback argument to ANY call (a cost provider by naming
    contract, whatever registers it). Same bodies unregistered stay
    un-flagged."""
    costz = """
def cost_page(server):
    return {"flops": float(server.state.sum())}  # syncs per scrape

def wire(http_cls, server):
    return http_cls(port=0, costz_fn=cost_page)
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(costz, path="orion_tpu/serving/dummy.py")
    )
    profilez = """
def wire(http_cls, engine):
    return http_cls(port=0, profilez_fn=lambda q: engine.state.item())
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(profilez, path="orion_tpu/serving/dummy.py")
    )
    named_cost = """
def chunk_cost(engine):
    return float(engine.state.sum())  # device sync in a cost provider

def wire(scheduler):
    scheduler.register(chunk_cost)  # ANY registration call claims it
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(named_cost, path="orion_tpu/fleet/dummy.py")
    )
    clean = """
def cost_page(server):
    return {"flops": server.flops_estimate, "ms": server.attributed_ms}

def chunk_cost(engine):
    return engine.tokens * engine.flops_per_token  # host mirrors only

def wire(http_cls, server, scheduler):
    scheduler.register(chunk_cost)
    return http_cls(port=0, costz_fn=cost_page,
                    capacity_fn=lambda: server.headroom)
"""
    assert "obs-device-sync" not in rule_ids(
        lint_source(clean, path="orion_tpu/serving/dummy.py")
    )
    # the identical sync-y bodies NOT registered anywhere stay un-flagged
    free = """
def cost_page(server):
    return {"flops": float(server.state.sum())}

def chunk_cost(engine):
    return float(engine.state.sum())
"""
    assert "obs-device-sync" not in rule_ids(
        lint_source(free, path="orion_tpu/serving/dummy.py")
    )


def test_obs_device_sync_bans_jax_imports_in_obs_package():
    """Inside orion_tpu/obs/ the jax IMPORT itself is the finding — a
    device array must be structurally unreachable from telemetry code,
    not just unpatterned; outside obs/ the import is of course fine."""
    src = """
from jax import numpy as jnp

def fmt(v):
    return str(v)
"""
    assert "obs-device-sync" in rule_ids(
        lint_source(src, path="orion_tpu/obs/trace_dummy.py")
    )
    assert "obs-device-sync" not in rule_ids(
        lint_source(src, path="orion_tpu/serving/dummy.py")
    )


def test_decode_host_sync_scoped_to_decode_modules():
    src = """
def drive(chunks):
    for c in chunks:
        c.block_until_ready()
"""
    # decode modules: serving/ and generate.py
    assert "decode-host-sync" in rule_ids(
        lint_source(src, path="orion_tpu/serving/session.py")
    )
    assert "decode-host-sync" in rule_ids(
        lint_source(src, path="orion_tpu/generate.py")
    )
    # host loops elsewhere (eval CLI, data prep) may sync freely
    assert "decode-host-sync" not in rule_ids(
        lint_source(src, path="orion_tpu/evaluate.py")
    )
    # probe-named functions are the designated sync points — even a loop
    # lexically inside one is exempt
    probed = """
def _probe_all_finite(carries):
    for c in carries:
        if not float(c.sum()):
            return False
    return True
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(probed, path="orion_tpu/serving/session.py")
    )


def test_decode_host_sync_budgets_one_probe_per_chunk_loop():
    """The probe exemption is itself budgeted for the scheduler loop:
    ONE probe sync per chunk regardless of slot count. Two probe calls in
    one loop body, or a probe inside a per-slot loop nested in the chunk
    loop, are findings; the single-probe scheduler shape is clean."""
    # clean: the continuous-batching scheduler's shape — one probe call
    # per chunk-loop iteration, however many slots are resident
    clean = """
def schedule(engine):
    while engine.busy:
        flags = engine._probe_slots()
        engine.evict(flags)
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(clean, path="orion_tpu/serving/batching.py")
    )
    # two probe calls per chunk loop = two device round-trips per chunk
    double = """
def schedule(engine):
    while engine.busy:
        finite = engine._probe_finite()
        done = engine._probe_done()
"""
    assert "decode-host-sync" in rule_ids(
        lint_source(double, path="orion_tpu/serving/batching.py")
    )
    # the per-slot-probe shape: syncs slot-count times per chunk
    nested = """
def schedule(engine, slots):
    while engine.busy:
        for i in range(slots):
            engine._probe_slot(i)
"""
    assert "decode-host-sync" in rule_ids(
        lint_source(nested, path="orion_tpu/serving/batching.py")
    )
    # outside the decode modules the budget does not apply
    assert "decode-host-sync" not in rule_ids(
        lint_source(double, path="orion_tpu/evaluate.py")
    )


def test_decode_host_sync_admission_path_is_sync_free():
    """ISSUE 7: in-scan prefill makes admission an O(1) slot insert, so a
    host sync inside an admit/insert/stage-named function of the engine
    is a finding even OUTSIDE a loop (a per-admit device round-trip on
    the scheduler's hot path is the stall the unified path kills)."""
    synced = """
import numpy as np

def admit(engine, prompt):
    state = engine.prefill(prompt)
    return np.asarray(state)

def _flush_staged(engine, prompt):
    return float(engine.park(prompt))
"""
    found = rule_ids(
        lint_source(synced, path="orion_tpu/serving/batching.py")
    )
    assert "decode-host-sync" in found
    # the clean O(1) shape: staging dispatches device work, syncs nothing
    clean = """
import jax.numpy as jnp

def admit(engine, prompt, i):
    row = jnp.pad(prompt, ((0, 0), (0, engine.width - prompt.shape[1])))
    engine.stage_row(row, i)
    return i

def _insert(engine, carry, i):
    return engine.write_row(carry, i)
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(clean, path="orion_tpu/serving/batching.py")
    )
    # the budget is the ENGINE's: admission helpers elsewhere (even other
    # decode modules) keep the loop-scoped rule only
    assert "decode-host-sync" not in rule_ids(
        lint_source(synced, path="orion_tpu/serving/server.py")
    )
    # probe-named designated syncs stay exempt inside the engine too
    probed = """
def _admit_probe(engine):
    return float(engine.flags())
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(probed, path="orion_tpu/serving/batching.py")
    )


def test_decode_host_sync_prefix_paths_are_admission_scope():
    """ISSUE 11: the prefix cache's lookup/stage/publish paths in the
    engine are admission code — hash + disk + one fused jitted dispatch
    only. A host sync inside a *prefix*-named function of
    serving/batching.py is a finding even outside a loop; the store-side
    serialization (prefix_store.py) is out of this rule's scope."""
    synced = """
import numpy as np

def _prefix_lookup(engine, request):
    key = engine.store.key_for(np.asarray(request.prompt))
    return engine.store.get(key)

def publish_pending_prefixes(engine):
    for key, row in engine.pending:
        state = engine.prefill(row)
        engine.store.put(key, np.asarray(state))
"""
    found = rule_ids(
        lint_source(synced, path="orion_tpu/serving/batching.py")
    )
    assert "decode-host-sync" in found
    # the clean shape: hashing and disk checks stay in the store, the
    # snapshot copy is one jitted row write, serialization is delegated
    clean = """
import jax.numpy as jnp

def _prefix_lookup(engine, request):
    return engine.store.lookup(request.prompt)  # hash + disk inside

def _stage_prefix(engine, prompt, entry, i):
    row = jnp.pad(prompt, ((0, 0), (0, engine.width - prompt.shape[1])))
    engine.stage_row(entry.state, row, i)  # one fused dispatch

def publish_pending_prefixes(engine):
    while engine.pending:
        key, row = engine.pending.pop(0)
        carry = engine.prefill(row)       # jitted dispatch, no readback
        engine.store.publish(row, carry[1])  # store owns the device_get
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(clean, path="orion_tpu/serving/batching.py")
    )
    # prefix-named helpers OUTSIDE the engine module keep loop scope
    # only: the store's publish-side serialization syncs (no loop) are
    # legal there by design
    store_side = """
import numpy as np

def publish_prefix(store, tokens, state):
    return store.write(np.asarray(state))  # the sanctioned device_get
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(store_side, path="orion_tpu/serving/prefix_store.py")
    )


def test_decode_host_sync_spec_paths_are_sync_free():
    """ISSUE 13: the self-speculation paths — draft pass, verify piece,
    spec-round bookkeeping — must make the accept/reject decision from
    the existing single per-chunk probe transfer. Any host sync inside a
    draft/verify/spec-named function of serving/batching.py is a finding
    even outside a loop; probe-named functions stay the designated sync
    point."""
    synced = """
import numpy as np

def _attempt_spec(engine, carry):
    out, toks, accepted = engine.spec_round(carry)
    return out, np.asarray(accepted)

def _draft_ahead(engine, carry):
    return float(engine.draft(carry))

def _verify_piece(engine, fed):
    return engine.logits(fed).item()
"""
    found = rule_ids(
        lint_source(synced, path="orion_tpu/serving/batching.py")
    )
    assert "decode-host-sync" in found
    assert len([f for f in lint_source(
        synced, path="orion_tpu/serving/batching.py"
    ) if f.rule == "decode-host-sync"]) == 3
    # the clean shape: the round dispatches device work; the accepted
    # counts come back through the probe's stacked transfer
    clean = """
import jax.numpy as jnp

def _attempt_spec(engine, carry, active):
    return engine.spec_round(carry, jnp.asarray(active))

def _update_spec_accept(engine, i, accepted):
    engine.ewma[i] = 0.5 * (engine.ewma[i] or accepted) + 0.5 * accepted

def spec_info(engine):
    return [dict(slot=i, on=bool(b)) for i, b in enumerate(engine.on)]

def _probe_bad_spec(engine, carry, accepted):
    import numpy as np
    return np.asarray(engine.flags(carry, accepted))  # designated sync
"""
    assert "decode-host-sync" not in rule_ids(
        lint_source(clean, path="orion_tpu/serving/batching.py")
    )
    # spec-named helpers OUTSIDE the engine module keep loop scope only
    assert "decode-host-sync" not in rule_ids(
        lint_source(synced, path="orion_tpu/serving/server.py")
    )


def test_loop_accum_only_fires_on_hot_paths():
    src = """
import jax.numpy as jnp

def helper(xs):
    out = jnp.zeros((0,))
    for x in xs:
        out = jnp.concatenate([out, x])
    return out
"""
    assert "loop-accum" in rule_ids(
        lint_source(src, path="orion_tpu/ops/feature_maps.py")
    )
    # cold paths (data prep, CLIs) may build arrays in Python loops
    assert "loop-accum" not in rule_ids(
        lint_source(src, path="orion_tpu/prepare_data.py")
    )


# -- suppression / baseline ---------------------------------------------------


def test_noqa_suppresses_specific_rule():
    src = """
def f(x):
    try:
        return x
    except:  # orion: noqa[bare-except]
        return None
"""
    assert "bare-except" not in rule_ids(lint_source(src, path="orion_tpu/d.py"))


def test_noqa_bare_suppresses_all_and_wrong_id_does_not():
    bare = """
def f(x, acc=[]):  # orion: noqa
    return acc
"""
    assert lint_source(bare, path="orion_tpu/d.py") == []
    wrong = """
def f(x, acc=[]):  # orion: noqa[bare-except]
    return acc
"""
    assert "mutable-default" in rule_ids(lint_source(wrong, path="orion_tpu/d.py"))


def test_baseline_filters_by_rule_and_path(tmp_path):
    src = """
def f(x, acc=[]):
    return acc
"""
    findings = lint_source(src, path="orion_tpu/d.py")
    assert findings
    base = [BaselineEntry("mutable-default", "orion_tpu/d.py", "fixture")]
    assert apply_baseline(findings, base) == []
    other = [BaselineEntry("mutable-default", "orion_tpu/other.py", "fixture")]
    assert apply_baseline(findings, other) == findings


def test_baseline_requires_reason(tmp_path):
    p = tmp_path / "baseline.json"
    p.write_text(json.dumps(
        {"entries": [{"rule": "bare-except", "path": "x.py", "reason": ""}]}
    ))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(p))


def test_signal_rule_sees_method_handlers():
    src = """
import signal

class Guard:
    def __enter__(self):
        signal.signal(signal.SIGTERM, self._handle)
        return self

    def _handle(self, signum, frame):
        self.stop = True
        self.ckpt.save(self.state)
"""
    assert "signal-unsafe-handler" in rule_ids(
        lint_source(src, path="orion_tpu/dummy.py")
    )


def test_signal_rule_catches_logger_idiom():
    src = """
import logging
import signal

log = logging.getLogger(__name__)
_STOP = False

def _handle(signum, frame):
    global _STOP
    _STOP = True
    log.warning("preempted")

signal.signal(signal.SIGTERM, _handle)
"""
    assert "signal-unsafe-handler" in rule_ids(
        lint_source(src, path="orion_tpu/dummy.py")
    )


def test_noqa_covers_full_multiline_statement():
    # the finding lands on the `acc=[]` physical line; the noqa trails the
    # closing paren two lines later — same LOGICAL line, must suppress
    trailing = """
def f(
    x,
    acc=[],
):  # orion: noqa[mutable-default]
    return acc
"""
    assert lint_source(trailing, path="orion_tpu/d.py") == []
    # and the reverse: noqa on the opening line of a call whose flagged
    # argument sits on a later physical line
    leading = """
import jax.numpy as jnp

def f(x):
    return jnp.asarray(  # orion: noqa[float64-literal]
        1.0,
        dtype="float64",
    )
"""
    assert lint_source(leading, path="orion_tpu/d.py") == []
    # a bare noqa on a def HEADER must not mute findings in the body
    body_not_muted = """
def f(x):  # orion: noqa
    try:
        return x
    except:
        return None
"""
    assert "bare-except" in rule_ids(
        lint_source(body_not_muted, path="orion_tpu/d.py")
    )


def test_keep_suppressed_marks_status():
    src = """
def f(x, acc=[]):  # orion: noqa[mutable-default]
    return acc

def g(x, table={}):
    return table
"""
    findings = lint_source(src, path="orion_tpu/d.py", keep_suppressed=True)
    by_status = {f.status for f in findings}
    assert by_status == {"suppressed", "active"}
    # default path still drops them
    assert all(
        f.status == "active"
        for f in lint_source(src, path="orion_tpu/d.py")
    )


# ---------------------------------------------------------------------------
# Tier B: jaxpr contracts — seeded violations vs clean toys
# ---------------------------------------------------------------------------


def test_collective_in_decode_flagged():
    jx = jax.make_jaxpr(
        lambda x: jax.lax.psum(x, "i"), axis_env=[("i", 2)]
    )(jnp.ones((4,)))
    findings = jaxpr_audit.audit_no_collectives(jx, "decode")
    assert rule_ids(findings) == {jaxpr_audit.CONTRACT_DECODE_COLLECTIVES}


def test_collective_free_fn_passes():
    jx = jax.make_jaxpr(lambda x: (x * 2).sum())(jnp.ones((4,)))
    assert jaxpr_audit.audit_no_collectives(jx, "decode") == []


def test_f32_upcast_in_bf16_step_flagged():
    def bad_step(a, b):
        # the deliberate silent upcast: bf16 inputs promoted to f32 matmul
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

    jx = jax.make_jaxpr(bad_step)(
        jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
    )
    findings = jaxpr_audit.audit_matmul_bf16(jx, "train")
    assert rule_ids(findings) == {jaxpr_audit.CONTRACT_BF16_MATMUL}


def test_bf16_matmul_with_f32_accum_passes():
    def good_step(a, b):
        return jnp.dot(a, b, preferred_element_type=jnp.float32)

    jx = jax.make_jaxpr(good_step)(
        jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
    )
    assert jaxpr_audit.audit_matmul_bf16(jx, "train") == []


def test_f32_matmul_in_declared_scope_passes():
    def state_accum(a, b):  # stands in for the fp32 kv-state contract
        return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32))

    jx = jax.make_jaxpr(state_accum)(
        jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
        jax.ShapeDtypeStruct((8, 8), jnp.bfloat16),
    )
    assert jaxpr_audit.audit_matmul_bf16(
        jx, "train", allowed_scopes=("test_analysis.py",)
    ) == []


def test_host_callback_flagged_and_clean_passes():
    def bad(x):
        jax.debug.print("x={}", x)
        return x * 2

    jx = jax.make_jaxpr(bad)(jnp.ones((4,)))
    findings = jaxpr_audit.audit_no_host_callbacks(jx, "decode")
    assert rule_ids(findings) == {jaxpr_audit.CONTRACT_HOST_CALLBACK}
    jx2 = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((4,)))
    assert jaxpr_audit.audit_no_host_callbacks(jx2, "decode") == []


def _toy_decode_jaxpr(state_rows):
    """A decode-shaped scan whose carry is sized by ``state_rows`` — O(1)
    iff the caller passes the same value for every sequence length."""

    def fn(x):
        def body(carry, _):
            carry = carry.at[0].add(x.sum())
            return carry, carry[0]

        return jax.lax.scan(
            body, jnp.zeros((state_rows, 4)), None, length=state_rows
        )

    return jax.make_jaxpr(fn)(jnp.ones((4,)))


def test_growing_decode_state_flagged():
    findings = jaxpr_audit.audit_scan_state_invariance(
        [("n=4", _toy_decode_jaxpr(4)), ("n=8", _toy_decode_jaxpr(8))],
        "decode",
    )
    assert rule_ids(findings) == {jaxpr_audit.CONTRACT_DECODE_STATE}


def test_o1_decode_state_passes():
    def make(n):
        def fn(x):
            def body(carry, _):
                return carry * 0.5 + x.sum(), carry.sum()

            return jax.lax.scan(body, jnp.zeros((4, 4)), None, length=n)

        return jax.make_jaxpr(fn)(jnp.ones((4,)))

    assert jaxpr_audit.audit_scan_state_invariance(
        [("n=4", make(4)), ("n=8", make(8))], "decode"
    ) == []


def test_scanless_decode_is_itself_a_finding():
    jx = jax.make_jaxpr(lambda x: x * 2)(jnp.ones((4,)))
    findings = jaxpr_audit.audit_scan_state_invariance([("n=4", jx)], "decode")
    assert rule_ids(findings) == {jaxpr_audit.CONTRACT_DECODE_STATE}


# -- the real repo entrypoints are the negative cases ------------------------


@pytest.fixture(scope="module")
def decode_jaxprs():
    return (
        jaxpr_audit.trace_decode(8, 8),
        jaxpr_audit.trace_decode(16, 16),
    )


def test_repo_decode_contracts(decode_jaxprs):
    small, large = decode_jaxprs
    assert jaxpr_audit.audit_no_collectives(small, "decode") == []
    assert jaxpr_audit.audit_no_host_callbacks(small, "decode") == []
    assert jaxpr_audit.audit_scan_state_invariance(
        [("small", small), ("large", large)], "decode"
    ) == []


def test_repo_train_step_bf16_policy():
    jx = jaxpr_audit.trace_train_step()
    from orion_tpu.models.configs import F32_MATMUL_SCOPES

    assert jaxpr_audit.audit_matmul_bf16(
        jx, "train", allowed_scopes=F32_MATMUL_SCOPES
    ) == []
    assert jaxpr_audit.audit_no_host_callbacks(jx, "train") == []
    # the declared-exception list is load-bearing: with it emptied, the
    # fp32 kv-state matmuls MUST be flagged (proves the auditor sees them)
    undeclared = jaxpr_audit.audit_matmul_bf16(jx, "train", allowed_scopes=())
    assert rule_ids(undeclared) == {jaxpr_audit.CONTRACT_BF16_MATMUL}


def test_repo_lra_step_traces_clean():
    jx = jaxpr_audit.trace_lra_step()
    assert jaxpr_audit.audit_no_host_callbacks(jx, "lra") == []


# ---------------------------------------------------------------------------
# Tier C part 1: SPMD collective budgets — toys vs the declared budgets
# ---------------------------------------------------------------------------

from orion_tpu.analysis import snapshots, spmd_audit
from orion_tpu.parallel.budgets import BUDGETS, Allow, StepBudget


def _toy_budget(**kw):
    defaults = dict(prim="psum", max_count=2, dtypes=("float32",))
    defaults.update(kw)
    return StepBudget(step="toy", allows=(Allow(**defaults),))


def _psum_in_scan_jaxpr():
    def fn(x):
        def body(c, _):
            return c + jax.lax.psum(x, "i"), c.sum()

        return jax.lax.scan(body, jnp.zeros((4,)), None, length=4)

    return jax.make_jaxpr(fn, axis_env=[("i", 2)])(jnp.ones((4,)))


def _psum_outside_scan_jaxpr(n=1):
    def fn(x):
        for _ in range(n):
            x = jax.lax.psum(x, "i")
        return x

    return jax.make_jaxpr(fn, axis_env=[("i", 2)])(jnp.ones((4,)))


def test_extract_collectives_scope_and_dtype():
    sites = spmd_audit.extract_collectives(_psum_in_scan_jaxpr(), "toy")
    assert [s.prim for s in sites] == ["psum"]
    assert sites[0].in_loop and sites[0].dtypes == ("float32",)
    sites = spmd_audit.extract_collectives(_psum_outside_scan_jaxpr(), "toy")
    assert [s.in_loop for s in sites] == [False]
    assert sites[0].payload_bytes == 16  # f32[4]


def test_budget_dtype_checks_every_operand():
    # a psum over a (bf16, f32) tuple — one eqn with two operands, or (the
    # installed jax) one eqn per dtype: either way the f32 payload must not
    # hide behind the first operand's dtype
    def fn(a, b):
        return jax.lax.psum((a, b), "i")

    jx = jax.make_jaxpr(fn, axis_env=[("i", 2)])(
        jnp.ones((4,), jnp.bfloat16), jnp.ones((4,), jnp.float32)
    )
    sites = spmd_audit.extract_collectives(jx, "toy")
    assert {d for s in sites for d in s.dtypes} == {"bfloat16", "float32"}
    findings = spmd_audit.check_budget(
        sites, _toy_budget(dtypes=("bfloat16",)), "toy"
    )
    assert rule_ids(findings) == {spmd_audit.RULE_DTYPE}
    assert spmd_audit.check_budget(
        sites, _toy_budget(dtypes=("bfloat16", "float32")), "toy"
    ) == []


def test_budget_unbudgeted_collective_flagged():
    sites = spmd_audit.extract_collectives(_psum_outside_scan_jaxpr(), "toy")
    findings = spmd_audit.check_budget(
        sites, StepBudget(step="toy"), "toy"
    )
    assert rule_ids(findings) == {spmd_audit.RULE_UNBUDGETED}


def test_budget_over_count_flagged():
    sites = spmd_audit.extract_collectives(_psum_outside_scan_jaxpr(3), "toy")
    findings = spmd_audit.check_budget(
        sites, _toy_budget(max_count=2), "toy"
    )
    assert rule_ids(findings) == {spmd_audit.RULE_COUNT}


def test_budget_wrong_dtype_flagged():
    sites = spmd_audit.extract_collectives(_psum_outside_scan_jaxpr(), "toy")
    findings = spmd_audit.check_budget(
        sites, _toy_budget(dtypes=("bfloat16",)), "toy"
    )
    assert rule_ids(findings) == {spmd_audit.RULE_DTYPE}


def test_budget_hoistable_in_scan_flagged():
    sites = spmd_audit.extract_collectives(_psum_in_scan_jaxpr(), "toy")
    findings = spmd_audit.check_budget(
        sites, _toy_budget(hoistable=True), "toy"
    )
    assert rule_ids(findings) == {spmd_audit.RULE_IN_SCAN}
    # the same collective is fine when the budget says it belongs in a loop
    assert spmd_audit.check_budget(
        sites, _toy_budget(hoistable=False), "toy"
    ) == []


def test_budgets_and_targets_stay_in_sync():
    assert set(spmd_audit.SPMD_TARGETS) == set(BUDGETS), (
        "every SPMD trace target needs a budget in parallel/budgets.py "
        "and vice versa"
    )


def test_repo_spmd_budgets_clean():
    findings = spmd_audit.audit_spmd()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_injected_over_budget_collective_gates(monkeypatch):
    """Shrinking the ring budget to one ppermute must trip the auditor on
    the real trace — proof it sees the actual collectives — and must make
    the CLI exit non-zero."""
    tight = StepBudget(
        step="ring_attention_causal",
        allows=(Allow("ppermute", max_count=1, dtypes=("bfloat16",)),),
    )
    doctored = dict(BUDGETS, ring_attention_causal=tight)
    findings = spmd_audit.audit_spmd(budgets=doctored)
    assert spmd_audit.RULE_COUNT in rule_ids(findings)

    from orion_tpu.analysis.__main__ import main
    from orion_tpu.parallel import budgets as budgets_mod

    monkeypatch.setitem(
        budgets_mod.BUDGETS, "ring_attention_causal", tight
    )
    assert main(["--tier", "spmd"]) == 1
    monkeypatch.undo()
    assert main(["--tier", "spmd"]) == 0


# ---------------------------------------------------------------------------
# Tier C part 2: golden compile-artifact snapshots
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fresh_snapshots():
    """Build each snapshot target once (two tiny-model compiles) and share
    across every golden test."""
    return {name: snapshots.build_snapshot(name)
            for name in snapshots.SNAPSHOT_TARGETS}


def test_checked_in_golden_matches_fresh_build(fresh_snapshots):
    """The determinism + drift gate in one: a fresh CPU build must
    byte-match the committed golden files."""
    findings = snapshots.audit_golden(fresh=fresh_snapshots)
    assert findings == [], "\n".join(f.format() for f in findings)


def test_hand_edited_golden_is_a_finding(tmp_path, fresh_snapshots):
    for name, snap in fresh_snapshots.items():
        snapshots.write_golden(name, snap, str(tmp_path))
    edited = dict(fresh_snapshots["train_tiny_dp8"])
    edited["flops"] = edited["flops"] + 1
    snapshots.write_golden("train_tiny_dp8", edited, str(tmp_path))
    findings = snapshots.audit_golden(
        golden_dir=str(tmp_path), fresh=fresh_snapshots
    )
    assert rule_ids(findings) == {snapshots.RULE_DRIFT}
    assert "flops" in findings[0].message


def test_missing_golden_is_a_finding(tmp_path, fresh_snapshots):
    findings = snapshots.audit_golden(
        golden_dir=str(tmp_path), fresh=fresh_snapshots
    )
    assert rule_ids(findings) == {snapshots.RULE_MISSING}
    assert len(findings) == len(snapshots.SNAPSHOT_TARGETS)


def test_update_golden_round_trips(tmp_path, fresh_snapshots):
    assert snapshots.audit_golden(
        update=True, golden_dir=str(tmp_path), fresh=fresh_snapshots
    ) == []
    assert snapshots.audit_golden(
        golden_dir=str(tmp_path), fresh=fresh_snapshots
    ) == []


def test_quant_decode_goldens_pin_the_serving_contract(fresh_snapshots):
    """ISSUE 11: the int8/int4 batched-decode artifacts pin (a) ZERO
    collectives (quantized decode still never communicates), (b) scan
    carry bytes EXACTLY equal to the fp32 target's — only weights
    quantize; the O(1) state must not grow or shrink with qmode — and
    (c) real s8 traffic in the compiled program (the dequant feeds the
    same dots the fp32 path runs), which the fp32 target must NOT show."""
    fp32 = fresh_snapshots["decode_batched_tiny"]
    for name in ("decode_batched_int8", "decode_batched_int4"):
        snap = fresh_snapshots[name]
        assert all(v == 0 for v in snap["hlo_collectives"].values()), name
        assert snap["scan_carry_bytes"] == fp32["scan_carry_bytes"], (
            name, "the decode carry must be qmode-invariant"
        )
        assert snap["dtype_counts"].get("s8", 0) > 0, (
            name, "no int8 buffers in a quantized program?"
        )
        assert snap["op_histogram"].get("dot", 0) > 0, name
    assert fp32["dtype_counts"].get("s8", 0) == 0, (
        "the fp32 decode program must not stream int8"
    )
    # the int4 program carries the split-nibble signature: off-TPU the
    # packed kernel lowers to the even/odd half-dot pair (quant.py), so
    # its dot count strictly exceeds int8's single-dot-per-matmul form
    assert (fresh_snapshots["decode_batched_int4"]["op_histogram"]["dot"]
            > fresh_snapshots["decode_batched_int8"]["op_histogram"]["dot"])


def test_spec_decode_golden_pins_the_verify_contract(fresh_snapshots):
    """ISSUE 13: the speculative-round artifact pins (a) ZERO
    collectives — the draft pass and the batched verify piece never
    communicate — and (b) a largest scan carry that does NOT exceed the
    plain batched decode's: the draft scan threads shadow copies of the
    carry's own (S, z) rows (no growth — speculation adds no state) and
    the verify's inner scans carry one layer's state at a time."""
    spec = fresh_snapshots["decode_batched_spec_tiny"]
    plain = fresh_snapshots["decode_batched_tiny"]
    assert all(v == 0 for v in spec["hlo_collectives"].values()), (
        "the verify step must not communicate"
    )
    assert spec["scan_carry_bytes"] <= plain["scan_carry_bytes"], (
        "speculation must not grow the decode carry: the draft rides "
        "the SAME (S, z)"
    )
    assert spec["spec_depth"] == 4 and spec["slots"] == 8


def test_tp_decode_goldens_pin_the_megatron_contract(fresh_snapshots):
    """ISSUE 14: the tp=2/tp=4 batched-decode artifacts pin (a) the
    per-step collective budget EXACTLY — two all-reduces per block per
    decode step (wo + down, the Megatron intra-layer contract) and NO
    other collective kind: a third one is a leaked per-token cost no CPU
    parity test would catch; (b) per-device scan-carry bytes = the
    head-sharded state / tp plus ONLY the replicated per-slot
    bookkeeping vectors (a few dozen bytes — asserted against the
    unsharded target, slack documented); (c) the logical program
    (jaxpr-level carry) unchanged by placement."""
    from orion_tpu.models.configs import get_config
    from orion_tpu.parallel.decode import DECODE_ALLREDUCES_PER_BLOCK

    plain = fresh_snapshots["decode_batched_tiny"]
    n_blocks = get_config("tiny").n_layers
    slots = plain["slots"]
    vec_slack = slots * (3 * 4 + 1)  # token/t/emit int32 + done bool
    for tp in (2, 4):
        snap = fresh_snapshots[f"decode_batched_tp{tp}"]
        coll = snap["hlo_collectives"]
        assert coll["all-reduce"] == (
            DECODE_ALLREDUCES_PER_BLOCK * n_blocks
        ), (tp, coll)
        assert all(
            v == 0 for k, v in coll.items() if k != "all-reduce"
        ), (tp, coll)
        # the LOGICAL carry is placement-invariant...
        assert snap["scan_carry_bytes"] == plain["scan_carry_bytes"]
        # ...and the per-device share divides by tp up to the replicated
        # per-slot vectors
        per_dev = snap["scan_carry_bytes_per_device"]
        assert per_dev <= plain["scan_carry_bytes"] // tp + vec_slack, (
            tp, per_dev, plain["scan_carry_bytes"]
        )
        assert per_dev < plain["scan_carry_bytes"], tp
        assert snap["mesh"] == {"tp": tp}
        # weights actually sharded: per-device param bytes strictly
        # below the tp=2 < unsharded relation is pinned transitively
        assert snap["param_bytes_per_device"] > 0
    assert (fresh_snapshots["decode_batched_tp4"]["param_bytes_per_device"]
            < fresh_snapshots["decode_batched_tp2"]["param_bytes_per_device"])


def test_donated_arg_aliasing_recorded_and_checked(fresh_snapshots):
    # the dp8 train step donates its whole TrainState; XLA must alias it
    d = fresh_snapshots["train_tiny_dp8"]["donation"]
    assert d["donated_args"] > 0 and d["aliased"] >= d["donated_args"]
    # a snapshot where XLA refused the aliases is a finding even if golden
    refused = {
        "target": "toy", "donation": {"donated_args": 3, "aliased": 0},
    }
    assert rule_ids(snapshots.donation_findings(refused, "x.json")) == {
        snapshots.RULE_DONATION
    }
    ok = {"target": "toy", "donation": {"donated_args": 3, "aliased": 3}}
    assert snapshots.donation_findings(ok, "x.json") == []


def test_golden_cli_exit_codes(tmp_path, fresh_snapshots, monkeypatch):
    """CLI-level acceptance: --tier golden exits non-zero on a hand-edited
    snapshot and zero on a faithful one (snapshot build stubbed to the
    fixture's artifacts so the CLI test doesn't recompile)."""
    from orion_tpu.analysis.__main__ import main

    monkeypatch.setattr(
        snapshots, "build_snapshot", lambda name: fresh_snapshots[name]
    )
    for name, snap in fresh_snapshots.items():
        snapshots.write_golden(name, snap, str(tmp_path))
    assert main(["--tier", "golden", "--golden-dir", str(tmp_path)]) == 0
    edited = dict(fresh_snapshots["decode_tiny"])
    edited["scan_carry_bytes"] = edited["scan_carry_bytes"] + 64
    snapshots.write_golden("decode_tiny", edited, str(tmp_path))
    assert main(["--tier", "golden", "--golden-dir", str(tmp_path)]) == 1


def test_decode_snapshot_carries_o1_state(fresh_snapshots):
    # the decode artifact's scan carry is the per-token state budget — it
    # must exist and be small (tiny config: tens of KB, not activations)
    carry = fresh_snapshots["decode_tiny"]["scan_carry_bytes"]
    assert carry is not None and 0 < carry < 1 << 20


# ---------------------------------------------------------------------------
# The gate itself: repo clean, CLI exit codes
# ---------------------------------------------------------------------------


def test_repo_lint_clean():
    import orion_tpu

    from orion_tpu.analysis.lint import lint_paths

    root = os.path.dirname(os.path.dirname(os.path.abspath(orion_tpu.__file__)))
    findings = lint_paths(
        [os.path.dirname(os.path.abspath(orion_tpu.__file__))],
        baseline=load_baseline(),
        root=root,
    )
    assert findings == [], "\n".join(f.format() for f in findings)


def test_repo_jaxpr_audit_clean():
    findings = jaxpr_audit.audit_repo()
    assert findings == [], "\n".join(f.format() for f in findings)


def test_cli_exits_zero_on_clean_and_nonzero_on_finding(tmp_path):
    from orion_tpu.analysis.__main__ import main

    clean = tmp_path / "orion_clean.py"
    clean.write_text("def f(x):\n    return x\n")
    assert main([str(clean), "--tier", "lint"]) == 0

    bad = tmp_path / "orion_bad.py"
    bad.write_text("def f(x, acc=[]):\n    return acc\n")
    assert main([str(bad), "--tier", "lint"]) == 1


def test_cli_list_rules():
    from orion_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0


def test_cli_json_format_includes_suppressed(tmp_path, capsys):
    from orion_tpu.analysis.__main__ import main

    mod = tmp_path / "orion_mixed.py"
    mod.write_text(
        "def f(x, acc=[]):\n"
        "    return acc\n"
        "\n"
        "def g(x, table={}):  # orion: noqa[mutable-default]\n"
        "    return table\n"
    )
    rc = main([str(mod), "--tier", "lint", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1  # one ACTIVE finding gates; the suppressed one doesn't
    assert doc["counts"] == {"active": 1, "suppressed": 1, "baselined": 0}
    by_status = {f["status"]: f for f in doc["findings"]}
    assert by_status["active"]["rule"] == "mutable-default"
    assert {"rule", "path", "line", "message", "status"} <= set(
        by_status["suppressed"]
    )

    clean = tmp_path / "orion_clean2.py"
    clean.write_text("def f(x):\n    return x\n")
    capsys.readouterr()
    assert main([str(clean), "--tier", "lint", "--format", "json"]) == 0
    assert json.loads(capsys.readouterr().out)["counts"]["active"] == 0


@pytest.mark.slow
def test_cli_subprocess_whole_repo_exits_zero():
    import orion_tpu

    root = os.path.dirname(os.path.dirname(os.path.abspath(orion_tpu.__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "orion_tpu.analysis"],
        cwd=root, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr



# ---------------------------------------------------------------------------
# Tier E gate + per-tier summary + staleness audit (ISSUE 18)
# ---------------------------------------------------------------------------


def test_tier_e_whole_repo_clean_within_budget():
    """Tier E (with the memoized lowering pass) over the real tree: zero
    findings, cold run inside the 45s budget, memoized rerun near-free.
    This IS the tier-1 quick gate for the compile-universe audit."""
    import time

    from orion_tpu.analysis import program_audit

    program_audit._PLAN_MEMO.clear()
    t0 = time.perf_counter()
    findings = program_audit.audit_programs()
    cold = time.perf_counter() - t0
    assert findings == [], "\n".join(f.format() for f in findings)
    assert cold < 45.0, f"Tier E cold run took {cold:.1f}s (budget 45s)"
    t0 = time.perf_counter()
    program_audit.audit_programs()
    warm = time.perf_counter() - t0
    assert warm < 10.0, f"memoized Tier E rerun took {warm:.1f}s"


def test_cli_tier_programs_exits_zero_with_self_time(capsys):
    """Acceptance: `--tier programs` exits 0 on the repo, and --self-time
    covers Tier E."""
    from orion_tpu.analysis.__main__ import main

    rc = main(["--tier", "programs", "--self-time"])
    out = capsys.readouterr()
    assert rc == 0, out.out + out.err
    assert "self-time: tier E" in out.err
    assert "self-time: total" in out.err


def test_cli_json_per_tier_summary_trailer(tmp_path, capsys):
    """The json document carries a per-tier "tiers" trailer with counts
    and wall time — pinned so CI consumers can rely on the shape."""
    from orion_tpu.analysis.__main__ import main

    mod = tmp_path / "orion_tiers.py"
    mod.write_text(
        "def f(x=[]):\n"
        "    return x\n"
        "def g(x=[]):  # orion: noqa[mutable-default]\n"
        "    return x\n"
    )
    rc = main([str(mod), "--tier", "lint", "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert [t["tier"] for t in doc["tiers"]] == ["lint"]
    row = doc["tiers"][0]
    assert row["label"] == "tier A"
    assert row["active"] == 1
    assert row["suppressed"] == 1
    assert row["baselined"] == 0
    assert row["seconds"] >= 0.0


def test_tier_summary_lines_format():
    from orion_tpu.analysis.__main__ import tier_summary_lines

    rows = [
        {"tier": "lint", "label": "tier A", "active": 1, "suppressed": 2,
         "baselined": 0, "seconds": 0.125},
        {"tier": "programs", "label": "tier E", "active": 0,
         "suppressed": 0, "baselined": 0, "seconds": 3.5},
    ]
    lines = tier_summary_lines(rows)
    assert lines[0].startswith("tier")
    assert set(lines[1]) == {"-"}
    assert "tier A" in lines[2] and "0.12" in lines[2]
    assert "tier E" in lines[3] and "3.50" in lines[3]


def test_stale_noqa_both_directions(tmp_path):
    """A noqa that suppresses a real finding is alive; one on a clean
    line is itself a finding. Judged from the keep-suppressed finding
    set, comments located by TOKENIZING (docstrings that merely mention
    the pattern are not suppressions)."""
    from orion_tpu.analysis.staleness import (
        RULE_STALE_NOQA,
        stale_noqa_findings,
    )

    live = tmp_path / "orion_live.py"
    live.write_text(
        "def f(x=[]):  # orion: noqa[mutable-default]\n"
        "    return x\n"
    )
    findings = lint_source(
        live.read_text(), str(live), keep_suppressed=True
    )
    assert {f.status for f in findings} == {"suppressed"}
    assert stale_noqa_findings(
        findings, [str(live)], ALL_RULES.keys()
    ) == []

    stale_mod = tmp_path / "orion_stale.py"
    stale_mod.write_text(
        '"""mentions # orion: noqa[mutable-default] in prose only."""\n'
        "def f(x):  # orion: noqa[mutable-default]\n"
        "    return x\n"
    )
    found = stale_noqa_findings(
        lint_source(stale_mod.read_text(), str(stale_mod),
                    keep_suppressed=True),
        [str(stale_mod)], ALL_RULES.keys(),
    )
    assert [f.rule for f in found] == [RULE_STALE_NOQA]
    assert found[0].line == 2  # the comment, not the docstring mention


def test_stale_noqa_scoping_rules(tmp_path):
    """Ids of rules that did NOT run are never judged; bare noqa and
    unknown ids are judged only on a full run."""
    from orion_tpu.analysis.staleness import stale_noqa_findings

    mod = tmp_path / "orion_scope.py"
    mod.write_text(
        "def f(x):  # orion: noqa[lock-order]\n"
        "    return x\n"
        "def g(x):  # orion: noqa\n"
        "    return x\n"
        "def h(x):  # orion: noqa[no-such-rule]\n"
        "    return x\n"
    )
    findings = lint_source(mod.read_text(), str(mod), keep_suppressed=True)
    # Tier A run: the Tier D id, the bare noqa, and the typo are out of scope
    assert stale_noqa_findings(
        findings, [str(mod)], ALL_RULES.keys()
    ) == []
    # full run with Tier D ids in the judging set: all three are findings
    full = stale_noqa_findings(
        findings, [str(mod)],
        list(ALL_RULES.keys()) + ["lock-order"], full=True,
    )
    assert len(full) == 3


def test_dead_baseline_entry_and_prune_round_trip(tmp_path, capsys):
    """A baseline entry whose finding is fixed becomes a finding itself;
    --prune-baseline rewrites the file keeping the live entry (and its
    rationale) verbatim."""
    from orion_tpu.analysis.__main__ import main
    from orion_tpu.analysis.findings import normalize_path

    mod = tmp_path / "orion_bl.py"
    mod.write_text("def f(x=[]):\n    return x\n")
    rel = normalize_path(str(mod))
    bl = tmp_path / "baseline.json"
    entries = [
        {"rule": "mutable-default", "path": rel,
         "reason": "fixture: grandfathered on purpose"},
        {"rule": "bare-except", "path": rel,
         "reason": "fixture: nothing left to grandfather"},
    ]
    bl.write_text(json.dumps({"entries": entries}))

    # the dead entry gates...
    rc = main([str(mod), "--tier", "lint", "--baseline", str(bl),
               "--format", "json"])
    doc = json.loads(capsys.readouterr().out)
    assert rc == 1
    by_rule = {f["rule"] for f in doc["findings"]}
    assert "dead-baseline-entry" in by_rule
    dead_msgs = [f["message"] for f in doc["findings"]
                 if f["rule"] == "dead-baseline-entry"]
    assert len(dead_msgs) == 1 and "bare-except" in dead_msgs[0]
    assert doc["counts"]["baselined"] == 1  # the live entry still matches

    # ...and --prune-baseline removes exactly it, preserving the live one
    rc = main([str(mod), "--tier", "lint", "--baseline", str(bl),
               "--prune-baseline"])
    capsys.readouterr()
    assert rc == 0
    pruned = json.loads(bl.read_text())
    assert pruned["entries"] == [entries[0]]
    # idempotent: a second run is clean without touching the file again
    assert main([str(mod), "--tier", "lint", "--baseline", str(bl)]) == 0


def test_dead_baseline_entry_scoping():
    """Entries are judged only when their rule ran AND their file was in
    the audited path set — a partial run must not call baselines dead."""
    from orion_tpu.analysis.findings import BaselineEntry as BE
    from orion_tpu.analysis.staleness import dead_baseline_entries

    entries = [
        BE("mutable-default", "orion_tpu/a.py", "r"),
        BE("lock-order", "orion_tpu/serving/b.py", "r"),
    ]
    # lint ran over orion_tpu/: the Tier D entry is out of judging scope
    dead = dead_baseline_entries(
        [], entries, ALL_RULES.keys(), ["orion_tpu"]
    )
    assert dead == [entries[0]]
    # path outside the audited prefixes is never judged
    dead = dead_baseline_entries(
        [], entries, ALL_RULES.keys(), ["orion_tpu/serving"]
    )
    assert dead == []
