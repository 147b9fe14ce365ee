"""Cost attribution + capacity observability suite (ISSUE 15).

The acceptance proofs live here — (1) attribution is CONSERVATIVE:
across staggered admission, in-scan prefill, a ladder rung-1 replay,
and a speculative round, the per-request ``device_ms`` shares sum to
the total measured chunk wall time (float-exact; the tolerance covers
the 6-decimal stamping); (2) attribution is FREE: with the cost ledger,
capacity model, and profiler surfaces fully on, every decode/prefill
jit cache is exactly what the dark run left (the PR 9 zero-cost idiom —
the ledger harvest LOWERS, never compiles); (3) the capacity model
turns windowed chunk_ms quantiles into a tokens/s ceiling + headroom a
scale-out decision could key on, per replica and aggregated fleet-wide;
(4) ``python -m orion_tpu.obs.cost check`` gates a dumped snapshot on
headroom and the conservation residual (``no_data`` passes); (5) the
``/costz`` and ``/profilez`` endpoints serve the price sheet and arm
real ``jax.profiler`` captures that write linkable artifacts.
"""

import json
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import (
    SampleConfig,
    _decode_batched_chunk_jit,
    _decode_batched_prefill_chunk_jit,
    _prefill_carry_bucketed_jit,
)
from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM
from orion_tpu.obs import cost as obs_cost
from orion_tpu.obs.cost import (
    CapacityModel,
    CostLedger,
    attribute_chunk,
    check_snapshot_cost,
    fleet_capacity,
)
from orion_tpu.resilience import inject
from orion_tpu.serving import DecodeRequest, ServeConfig, Server

pytestmark = pytest.mark.chaos

CFG = ModelConfig(
    name="cost_test", vocab_size=64, d_model=32, n_layers=3, n_heads=2,
    layer_types=("linear", "softmax", "swa"), window=4, max_seq_len=96,
    dtype="float32", backend="xla",
)
GREEDY = SampleConfig(temperature=0.0)


@pytest.fixture(scope="module")
def mp():
    model = TransformerLM(CFG)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return model, params


def _prompt(i, ln=5):
    return jax.random.randint(
        jax.random.PRNGKey(4000 + i), (1, ln), 0, CFG.vocab_size
    ).astype(jnp.int32)


def _cfg(**kw):
    kw.setdefault("chunk", 4)
    kw.setdefault("slots", 2)
    kw.setdefault("max_inflight", 8)
    return ServeConfig(**kw)


def _conservation(srv, pendings):
    """|sum(per-request device_ms) - sum(chunk_ms)| / sum(chunk_ms)."""
    attributed = sum(p.result.device_ms for p in pendings)
    cell = srv._h_chunk_ms.cell_total()
    assert cell is not None and cell["sum"] > 0
    return abs(attributed - cell["sum"]) / cell["sum"]


# ---------------------------------------------------------------------------
# conservation under chaos (the acceptance property)
# ---------------------------------------------------------------------------


def test_attribution_conserves_under_stagger_prefill_and_ladder(mp):
    """Staggered admission + in-scan prefill + a rung-1 replay: every
    request's device_ms share sums to the measured chunk wall time, the
    ledger prices the programs it lowered, and the first-launch compile
    times land in the ledger."""
    model, params = mp
    srv = Server(model, params, _cfg(
        prefill_chunk=8, cost=True, cost_ledger=True,
    ))
    pendings = [
        srv.submit(DecodeRequest(
            prompt=_prompt(i, ln=4 + 2 * i), max_new_tokens=12,
            sample=GREEDY, seed=i,
        ))
        for i in range(3)  # 3 requests > 2 slots: the third joins late
    ]
    plan = inject.FaultPlan().poison_decode_slot_at(0, 2, times=1)
    with inject.inject(plan):
        assert srv.serve(drain_when_idle=True) == 0
    assert [p.result.status for p in pendings] == ["ok"] * 3
    assert sum(p.result.rewinds for p in pendings) >= 1, "rung 1 engaged"
    assert _conservation(srv, pendings) < 1e-6
    for p in pendings:
        r = p.result
        assert r.device_ms > 0 and r.cost_flops > 0
        assert r.decode_tokens == 12
        assert r.prefill_tokens == p.request.prompt.shape[-1], (
            "in-scan admission consumes exactly the prompt"
        )
    # the histograms observed one cost row per request
    assert srv._h_req_device_ms.cell()["count"] == 3
    assert srv._h_req_flops.cell()["count"] == 3
    # ledger: harvested flops for both programs this shape runs, and the
    # engine observed their first-launch compile times (CFG is unique to
    # this module, so the compiles happened here)
    entries = srv.cost_ledger.entries()
    kinds = {e["kind"] for e in entries.values()}
    assert {"decode_batched", "unified_prefill"} <= kinds
    assert all(e.get("flops", 0) > 0 for e in entries.values())
    assert srv.cost_ledger.compile_times(), "first-launch compiles observed"
    # prefill tokens weigh at least a decode step (ledger-derived)
    assert (srv.cost_ledger.flops_per_prefill_token()
            >= srv.cost_ledger.flops_per_decode_step() > 0)
    srv.close()


def test_attribution_conserves_spec_round(mp):
    """Speculative rounds bill a FIXED per-round cost per speculating
    slot (acceptance moves tokens, not device work) and conservation
    holds through them, the staged prompts' unified boundaries included."""
    model, params = mp
    srv = Server(model, params, _cfg(
        prefill_chunk=8, spec_depth=2, spec_min_accept=0.0,
        cost=True, cost_ledger=True,
    ))
    pendings = [
        srv.submit(DecodeRequest(
            prompt=_prompt(10 + i), max_new_tokens=10, sample=GREEDY,
            seed=i,
        ))
        for i in range(2)
    ]
    assert srv.serve(drain_when_idle=True) == 0
    assert [p.result.status for p in pendings] == ["ok"] * 2
    assert _conservation(srv, pendings) < 1e-6
    for p in pendings:
        # the device's tokens: the 10 served, overshot by less than one
        # round (the accepted prefix + 1, at most depth + 1)
        assert 10 <= p.result.decode_tokens < 10 + 3
        assert p.result.prefill_tokens == 5  # the staged prompt, one piece
    kinds = {e["kind"] for e in srv.cost_ledger.entries().values()}
    assert "spec_round" in kinds
    assert srv.cost_ledger.flops_per_spec_round() > 0
    srv.close()


def test_cost_surfaces_add_zero_compiles(mp, tmp_path):
    """THE free-ness acceptance: a warmed engine shape re-served with
    ledger + capacity + attribution + an armed-and-fired profiler
    capture leaves all three decode/prefill jit caches EXACTLY as the
    dark run left them (the harvest LOWERS, never compiles)."""
    model, params = mp

    def run(cfg, n=3):
        srv = Server(model, params, cfg)
        ps = [
            srv.submit(DecodeRequest(prompt=_prompt(20 + i, ln=3 + i),
                                     max_new_tokens=12, sample=GREEDY,
                                     seed=i))
            for i in range(n)
        ]
        if cfg.profile_dir:
            assert srv.arm_profile(2).get("armed") == 2
        assert srv.serve(drain_when_idle=True) == 0
        assert all(p.result.status == "ok" for p in ps)
        srv.close()
        return srv, ps

    srv, ps = run(_cfg(prefill_chunk=8, cost=False))  # dark warm-up
    assert all(p.result.device_ms == 0 for p in ps), (
        "cost off: results carry no attribution"
    )
    sizes = lambda: (  # noqa: E731
        _decode_batched_chunk_jit._cache_size(),
        _decode_batched_prefill_chunk_jit._cache_size(),
        _prefill_carry_bucketed_jit._cache_size(),
    )
    before = sizes()
    srv, ps = run(_cfg(
        prefill_chunk=8, cost=True, cost_ledger=True,
        profile_dir=str(tmp_path / "prof"),
    ))
    assert sizes() == before, "cost surfaces must add ZERO compiles"
    # and they actually ran — this wasn't a dark pass
    assert all(p.result.device_ms > 0 for p in ps)
    assert srv.cost_ledger.entries()
    events = {e["event"] for e in srv.flight.events("profile")}
    assert {"armed", "start", "stop"} <= events
    artifacts = [
        os.path.join(r, f)
        for r, _, fs in os.walk(str(tmp_path / "prof")) for f in fs
    ]
    assert artifacts, "the capture must leave a linkable artifact"


# ---------------------------------------------------------------------------
# units: the attribution rule and the capacity model
# ---------------------------------------------------------------------------


def test_attribute_chunk_weights_and_conservation_unit():
    ledger = CostLedger(slots=2, chunk=4, prefill_chunk=8, spec_depth=2,
                        fallback_flops_per_token=100.0)
    ledger.record("decode_batched", "decode_batched(k)", flops=800.0)
    ledger.record("unified_prefill", "unified_prefill(k)", flops=2400.0)
    # decode step = 800/(2*4) = 100; prefill token = (2400-800)/8 = 200
    assert ledger.flops_per_decode_step() == 100.0
    assert ledger.flops_per_prefill_token() == 200.0
    rows = [
        {"tag": "a", "decode_steps": 4, "prefill_tokens": 0,
         "decode_tokens": 4},
        {"tag": "b", "decode_steps": 0, "prefill_tokens": 8,
         "decode_tokens": 0},
        {"tag": "c", "frozen": True, "decode_steps": 0,
         "prefill_tokens": 0, "decode_tokens": 0},
    ]
    shares = attribute_chunk(ledger, 10.0, rows)
    assert sum(s for _, s, _ in shares) == pytest.approx(10.0, abs=1e-12)
    got = {e["tag"]: (s, f) for e, s, f in shares}
    assert got["c"] == (0.0, 0.0), "frozen rows bill nothing"
    assert got["b"][0] == pytest.approx(4 * got["a"][0]), (
        "8 prefill tokens at 200 flops vs 4 decode steps at 100"
    )
    # spec rounds: fixed per-round cost regardless of acceptance
    ledger.record("spec_round", "spec_round(k)", flops=900.0)
    spec_rows = [
        {"tag": "a", "spec_round": True, "decode_tokens": 3,
         "decode_steps": 0, "prefill_tokens": 0},
        {"tag": "b", "spec_round": True, "decode_tokens": 1,
         "decode_steps": 0, "prefill_tokens": 0},
    ]
    shares = attribute_chunk(ledger, 6.0, spec_rows)
    assert [s for _, s, _ in shares] == [3.0, 3.0], (
        "equal rounds bill equally however many drafts were accepted"
    )
    # degenerate all-frozen boundary still conserves (uniform split)
    shares = attribute_chunk(ledger, 2.0, [
        {"tag": "a", "frozen": True}, {"tag": "b", "frozen": True},
    ])
    assert [s for _, s, _ in shares] == [1.0, 1.0]
    # empty boundary: nothing to split
    assert attribute_chunk(ledger, 2.0, []) == []


def test_capacity_model_ceiling_and_headroom_unit():
    now = [0.0]
    buckets = (1.0, 2.0, 5.0, float("inf"))
    counts = [0, 0, 0, 0]
    tokens = [0.0]
    cap = CapacityModel(
        slots=2, chunk=4, buckets=buckets,
        read_chunk_counts=lambda: tuple(counts),
        read_tokens=lambda: tokens[0],
        clock=lambda: now[0], window_s=10.0, slice_s=1.0,
    )
    assert cap.tick()["no_data"] is True
    with pytest.raises(LookupError):
        cap.gauge("headroom")()
    # 2 boundaries/s, every chunk in the (1, 2] bucket -> p50 = 1.5 ms,
    # each boundary emits 4 tokens (one slot decoding of two)
    for _ in range(20):
        now[0] += 0.5
        counts[1] += 1
        tokens[0] += 4.0
        st = cap.tick()
    assert st["no_data"] is False
    # ceiling = slots*chunk*1000/p50 = 2*4*1000/1.5
    assert st["ceiling_tokens_per_s"] == pytest.approx(8000 / 1.5, rel=0.01)
    assert st["current_tokens_per_s"] == pytest.approx(8.0, rel=0.05)
    assert 0.99 <= st["headroom"] <= 1.0
    assert cap.gauge("headroom")() == st["headroom"]
    # saturate: current beyond the ceiling clamps headroom at 0
    for _ in range(20):
        now[0] += 0.5
        counts[1] += 1
        tokens[0] += 100000.0
        st = cap.tick()
    assert st["headroom"] == 0.0
    # the window forgets: idle time with no boundaries -> no_data again
    for _ in range(40):
        now[0] += 0.5
        st = cap.tick()
    assert st["no_data"] is True


def test_fleet_capacity_recomputes_headroom_from_sums():
    agg = {"gauges": [
        {"name": "capacity_tokens_per_s", "labels": {}, "value": 1000.0},
        {"name": "capacity_current_tokens_per_s", "labels": {},
         "value": 900.0},
        {"name": "capacity_tokens_per_s", "labels": {}, "value": 1000.0},
        {"name": "capacity_current_tokens_per_s", "labels": {},
         "value": 100.0},
        # the summed per-replica headroom gauge is present but IGNORED
        {"name": "capacity_headroom", "labels": {}, "value": 1.0},
    ]}
    cap = fleet_capacity(agg)
    assert cap["replicas_reporting"] == 2
    assert cap["ceiling_tokens_per_s"] == 2000.0
    assert cap["headroom"] == pytest.approx(0.5)
    assert fleet_capacity({"gauges": []})["no_data"] is True


# ---------------------------------------------------------------------------
# endpoints + the check gate
# ---------------------------------------------------------------------------


def _get(url, timeout=10.0):
    try:
        r = urllib.request.urlopen(url, timeout=timeout)
        return r.status, r.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_costz_and_profilez_endpoints(mp, tmp_path):
    model, params = mp
    srv = Server(model, params, _cfg(
        prefill_chunk=8, cost=True, cost_ledger=True, metrics_port=0,
        profile_dir=str(tmp_path / "prof"),
    ))
    url = f"http://127.0.0.1:{srv.http_port}"
    code, body = _get(url + "/profilez?chunks=2")
    assert code == 200 and json.loads(body)["armed"] == 2
    code, body = _get(url + "/profilez?chunks=1")
    assert code == 409, "one capture at a time"
    code, body = _get(url + "/profilez?chunks=bogus")
    assert code == 400
    p = srv.submit(DecodeRequest(prompt=_prompt(30), max_new_tokens=12,
                                 sample=GREEDY, seed=0))
    assert srv.serve(drain_when_idle=True) == 0
    assert p.result.status == "ok"
    code, body = _get(url + "/costz")
    assert code == 200
    assert "[ledger]" in body and "[capacity]" in body
    code, body = _get(url + "/costz.json")
    doc = json.loads(body)
    assert doc["enabled"] and doc["capacity"]["no_data"] is False
    assert doc["attribution"]["attributed_ms_total"] > 0
    # /metrics carries the capacity gauges + the attribution counter
    code, body = _get(url + "/metrics")
    assert "capacity_headroom" in body
    assert "attributed_ms_total" in body
    assert "cost_ledger_flops" in body
    # /statusz shows the operator-facing cost section
    code, body = _get(url + "/statusz")
    assert "[cost]" in body
    srv.close()
    # profiling disabled: /profilez refuses with 409
    srv2 = Server(model, params, _cfg(prefill_chunk=8, metrics_port=0))
    code, body = _get(f"http://127.0.0.1:{srv2.http_port}/profilez?chunks=2")
    assert code == 409 and "disabled" in json.loads(body)["error"]
    srv2.close()


def test_cost_check_cli_gates_a_dumped_snapshot(tmp_path, capsys):
    def snap(headroom=None, chunk_sum=None, attributed=None):
        doc = {"counters": [], "gauges": [], "histograms": []}
        if headroom is not None:
            doc["gauges"].append({"name": "capacity_headroom",
                                  "labels": {}, "value": headroom})
        if chunk_sum is not None:
            doc["histograms"].append({
                "name": "chunk_ms", "labels": {"tp": "1"},
                "buckets": [1, "+Inf"], "counts": [3, 0],
                "sum": chunk_sum, "count": 3,
            })
        if attributed is not None:
            doc["counters"].append({"name": "attributed_ms_total",
                                    "labels": {}, "value": attributed})
        return doc

    def run(doc, *args):
        path = str(tmp_path / "snap.json")
        with open(path, "w") as f:
            json.dump(doc, f)
        rc = obs_cost.main(["check", *args, path])
        capsys.readouterr()
        return rc

    # healthy: headroom above the floor, conservation exact
    assert run(snap(0.6, 100.0, 100.0), "--min-headroom", "0.5") == 0
    # headroom violation
    assert run(snap(0.2, 100.0, 100.0), "--min-headroom", "0.5") == 1
    # conservation violation (20% residual vs the 5% default bound)
    assert run(snap(0.9, 100.0, 80.0)) == 1
    # within the bound passes
    assert run(snap(0.9, 100.0, 99.0)) == 0
    # no data at all passes (a run that never served is not a violation)
    assert run(snap(), "--min-headroom", "0.9") == 0
    # the programmatic form agrees
    rows, ok = check_snapshot_cost(snap(), min_headroom=0.9)
    assert ok and all(r["status"] == "no_data" for r in rows)


def test_fleet_aggregates_capacity(mp):
    from orion_tpu.fleet.replica import LocalReplica
    from orion_tpu.fleet.supervisor import Supervisor

    model, params = mp

    def factory(name):
        return LocalReplica(
            model, params, _cfg(prefill_chunk=8, cost=True), name=name,
        ).start()

    sup = Supervisor(factory, 2).start()
    try:
        pendings = [
            sup.router.submit(DecodeRequest(
                prompt=_prompt(40 + i), max_new_tokens=8, sample=GREEDY,
                seed=i,
            ))
            for i in range(4)
        ]
        for p in pendings:
            assert p.wait(timeout=60.0) is not None
        # a status scrape can time out under box load and fall back to a
        # stale pre-serving last_status — retry briefly for the full set
        import time as _time

        for _ in range(20):
            agg = sup.aggregate_metrics()
            cap = agg["capacity"]
            if cap.get("replicas_reporting") == 2:
                break
            _time.sleep(0.25)
        assert cap.get("no_data") is not True
        assert cap["replicas_reporting"] == 2
        assert cap["ceiling_tokens_per_s"] > 0
        assert 0.0 <= cap["headroom"] <= 1.0
        # per-request attribution rode the status op too
        counters = {
            (r["name"]): r["value"] for r in agg["counters"]
            if not r["labels"]
        }
        assert counters.get("attributed_ms_total", 0) > 0
        assert counters.get("decode_tokens_total", 0) == 4 * 8
    finally:
        sup.drain_all(timeout=30.0)
