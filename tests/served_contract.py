"""What every served configuration's test file holds, once: the tiny model
taken from ``benchmark/configs/<name>.json`` (its ``model.rehearse`` block and
its ``reference`` wiring, the ones the cell rehearses with), the programs and
answers every test of a file shares, and the tests that each configuration
used to restate.

A served configuration's test file is::

    CASE = ServedCase("<name>", seq=..., logit_tol=..., over={...}, ...)
    served = served_fixture(CASE)

    class TestServed(ServedContract):
        case = CASE
        def published(self, cfg): ...   # the preset's published shape

and the tests of the configuration's own mechanism. Which contract tests a
configuration takes is said by its case's fields (``share=None``: no
share-sum test); what a configuration asserts beyond the shared form goes
into the ``after_*`` hooks. ``tests/conftest.py`` hands collection to
``ServedContract.parametrise`` (backends and engines a case).

Not collected (no ``test_`` prefix)."""

import dataclasses
import functools
import hashlib
import importlib
import json
import os
import subprocess
import sys
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from orion_tpu.generate import SampleConfig, generate
from orion_tpu.models.configs import CONFIGS, ModelConfig, get_config
from orion_tpu.models.mixers import MIXERS
from orion_tpu.models.moe import STAT_NAMES, MoEMLP, stats_vector
from orion_tpu.models.transformer import TransformerLM, init_decode_state
from orion_tpu.ops import dispatch
from orion_tpu.serving import DecodeRequest, ServeConfig, Server, SlotEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "benchmark") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))  # ``reference``, ``readers``

GREEDY = SampleConfig(temperature=0.0)
BACKENDS = ("xla", "pallas_interpret")


# -- the configuration's file ------------------------------------------------------


@functools.lru_cache(maxsize=None)
def config_file(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        return json.load(f)


def rehearsal_cfg(name: str, **over) -> ModelConfig:
    """The configuration's file at its rehearsal sizes, as the benchmark
    builds it (``benchmark/harness.py::model_config`` under ``--rehearse``):
    the one reader of ``model.rehearse`` under ``tests/``."""
    fields = dict(config_file(name)["model"])
    fields.update(fields.pop("rehearse"))
    fields.update(over)
    if fields.get("layer_types") is not None:
        fields["layer_types"] = tuple(fields["layer_types"])
    return ModelConfig(name=name, **fields)


def other_presets(name: str) -> list:
    """Every preset but ``name``: what a configuration's new fields must be
    absent from."""
    return [cfg for other, cfg in CONFIGS.items() if other != name]


class Because(NamedTuple):
    """An override that CHANGES a value of the ``rehearse`` block, and why."""
    value: Any
    why: str


class ByBackend(dict):
    """A case's value that differs by backend: ``{backend: value}``."""


def pick(value, backend):
    return value.get(backend) if isinstance(value, ByBackend) else value


@dataclasses.dataclass(frozen=True)
class Walk:
    """``prefill`` = pieces of ``prefill_extend`` = the ``decode_step`` walk."""
    n: Any                        # rows prefilled (ByBackend: the interpreter is slow)
    piece: int                    # rows a piece; the last one is right-padded
    steps: int = 0                # teacher-forced steps from the prompt's state ...
    steps_from: str = "prefill"   # ... as "prefill", the "padded" prefill or the "pieces" left it
    live: bool = False            # ... each handed ``live`` (a served mixture masks and counts rows)
    cold: int = 0                 # steps from nothing, one position for all rows
    against: str = "model"        # whose full forward: the "model"'s or the "reference"'s
    tol: Optional[float] = None   # on logits; the case's ``logit_tol`` if None
    padded: int = 0               # columns of padding a ``prefill_last`` must stop before
    states: Any = None            # assert_allclose's tolerances, pieces' state against prefill's
    cold_states: Any = None       # the same, the cold walk's state against prefill's
    cache_rows: bool = False      # compare K and V up to the rows written only
    backends: tuple = BACKENDS


@dataclasses.dataclass(frozen=True)
class Scan:
    """``chunk_split`` / ``chunk_merge`` of a scan that holds the cache."""
    n: int
    steps: int
    layer: int      # the layer whose split is named
    held: tuple
    carried: tuple


@dataclasses.dataclass(frozen=True)
class Share:
    """The expert-parallel cut: 16 experts of one router over 4 chips."""
    experts: tuple               # the expert leaves that are cut
    part_tol: float
    sum_tol: float


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    seed: int
    seconds: int = 3
    trace: int = 1


@dataclasses.dataclass(frozen=True)
class ServedCase:
    name: str                      # of ``benchmark/configs/<name>.json`` and the preset
    seq: int = 0                   # T of the two seeded sequences
    logit_tol: float = 0.0
    over: dict = dataclasses.field(default_factory=dict)        # on the ``rehearse`` block
    by_backend: dict = dataclasses.field(default_factory=dict)  # {backend: more overrides}
    constants: dict = dataclasses.field(default_factory=dict)   # on ``reference.constants``
    moved: tuple = ("scale",)      # leaves moved off their initial value, by a part of their path
    # test_model_matches_the_reference
    forward: tuple = ("xla",)
    forward_tol: Any = None        # ByBackend; the ``logit_tol`` if None
    floor: Optional[float] = None  # the reference's logits reach it: no comparison of zeros
    bites: Optional[dict] = None   # a reference without the selection reads differently
    walk: Optional[Walk] = None
    row_list: Optional[int] = None  # rows prefilled before the step with a row list
    scan: Optional[Scan] = None
    share: Optional[Share] = None
    # test_engine_serves_as_generate: (backend, donate[, overrides])
    engines: tuple = tuple((b, d) for d in (False, True) for b in BACKENDS)
    engine: dict = dataclasses.field(default_factory=lambda: dict(
        slots=4, chunk=4, prefill_buckets=(64, 128, 256), prefill_chunk=32))
    prompts: tuple = ((0, 0, 30), (1, 0, 90), (0, 20, 190))  # (sequence, from, to)
    max_new: int = 9
    served_gap: Optional[float] = None  # teacher-forced through the reference, if given
    server: bool = False
    cell: Optional[Cell] = None
    pins: dict = dataclasses.field(default_factory=dict)  # program -> hash of its jaxpr


def _same(a, b):
    return (tuple(a) if isinstance(a, list) else a) == (tuple(b) if isinstance(b, list) else b)


def tiny_cfg(case: ServedCase, backend: str = "xla", **over) -> ModelConfig:
    """The case's tiny model: the file's ``rehearse`` block, the case's
    overrides (one that changes the block says why), the backend's."""
    block = config_file(case.name)["model"]["rehearse"]
    fields = {}
    for key, value in case.over.items():
        if isinstance(value, Because):
            assert key in block and not _same(block[key], value.value) and value.why, key
            value = value.value
        else:
            assert key not in block or _same(block[key], value), (
                f"{case.name}.{key} changes the rehearse block: say why (Because)")
        fields[key] = value
    return rehearsal_cfg(case.name, **{
        **fields, "backend": backend, **case.by_backend.get(backend, {}), **over})


def spec_of(case: ServedCase, cfg: ModelConfig, **over) -> dict:
    """The reference's spec as the cell wires it
    (``benchmark/kinds/serve_ref.py::reference_spec``): the file's
    ``reference.spec`` mapping read off ``cfg``, its ``constants``, the
    case's."""
    ref = config_file(case.name)["reference"]
    spec = {key: getattr(cfg, field) for key, field in ref["spec"].items()}
    return {**spec, **ref.get("constants", {}), **case.constants, **over}


# -- what a file's tests share -----------------------------------------------------


class Programs(NamedTuple):
    """One backend's model and its jitted serving methods, ``f(params, ...)``."""
    cfg: ModelConfig
    model: TransformerLM
    prefill: Any
    prefill_last: Any
    piece: Any   # prefill_extend_step(piece, states, offset, length)
    group: Any   # prefill_extend_group
    step: Any    # decode_step(token, states, t[, rows[, live]])


class Run(NamedTuple):
    ids: list      # a request's served ids, in the order of the prompts
    seen: list     # ``before_boundary``'s readings
    counted: list  # ``after_boundary``'s
    engine: SlotEngine
    cfg: ModelConfig


class Served:
    """The file's ONE tiny model: seeded parameters (the ``moved`` leaves off
    their initial value, so that a norm left out or misplaced shows), two
    seeded sequences, the reference's logits and the model's, and, built once
    and kept, each backend's jitted programs and ``generate()``'s answers."""

    def __init__(self, case: ServedCase):
        self.case, self.cfg = case, tiny_cfg(case)
        self.ref = importlib.import_module(
            "reference." + config_file(case.name)["reference"]["module"])
        self.model = TransformerLM(self.cfg)
        self.toks = jax.random.randint(jax.random.key(1), (2, case.seq), 0, self.cfg.vocab_size)
        params = jax.jit(self.model.init)(jax.random.key(0), self.toks[:, :16])
        self.params = jax.tree_util.tree_map_with_path(
            lambda path, x: x + 0.3 * jax.random.normal(jax.random.key(len(str(path))), x.shape)
            if any(part in str(path) for part in case.moved) else x, params)
        self._programs = {}
        self.want = self.reference()  # op by op, as the reference is written
        self.forward = jax.jit(self.model.apply)  # the training forward, one program
        with jax.default_matmul_precision("highest"):
            self.got = self.forward(self.params, self.toks)

    def spec(self, cfg=None, **over) -> dict:
        return spec_of(self.case, cfg or self.cfg, **over)

    def reference(self, spec=None, params=None, toks=None):
        """The reference's full forward (of this model, if nothing else is given)."""
        with jax.default_matmul_precision("highest"):
            return self.ref.forward(
                spec or self.spec(), self.params if params is None else params,
                self.toks if toks is None else toks)

    def forced(self, prompt, ids):
        """The reference's logits at the positions of a served answer,
        teacher-forced through ONE full forward of prompt + answer: every
        request of the file right-padded to one length (the forward is
        causal), so that one compiled program serves them all."""
        width = max(len(p) for p in self.prompts) + self.case.max_new
        whole = jnp.concatenate([jnp.asarray(prompt), jnp.asarray(ids)])
        with jax.default_matmul_precision("highest"):
            logits = self._forward(self.params, jnp.pad(whole, (0, width - len(whole)))[None])
        return logits[0, len(prompt) - 1:len(whole) - 1]

    @functools.cached_property
    def _forward(self):
        return jax.jit(lambda p, t: self.ref.forward(self.spec(), p, t))

    @functools.cached_property
    def uncut_layer(self):
        """(the cfg, the seeded expert layer) of ``case.share``'s whole router."""
        whole = dataclasses.replace(self.cfg, n_experts=16, moe_router_width=16)
        params = jax.jit(TransformerLM(whole).init)(jax.random.key(3), jnp.zeros((1, 8), jnp.int32))
        return whole, params["params"]["block_1"]["mlp"]

    def differs(self, spec=None, params=None, factor=20):
        """``test_the_comparison_sees``' frame: the reference with a mechanism
        changed reads differently from the model by ``factor`` tolerances."""
        gap = float(jnp.abs(self.reference(spec, params) - self.got).max())
        assert gap > factor * self.case.logit_tol, gap

    def programs(self, backend: str = "xla", **over) -> Programs:
        key = (backend, tuple(sorted(over.items())))
        if key not in self._programs:
            cfg = dataclasses.replace(
                self.cfg, backend=backend, **{**self.case.by_backend.get(backend, {}), **over})
            model = TransformerLM(cfg)

            def jit(method):
                return jax.jit(lambda p, *a: model.apply(p, *a, method=method))

            self._programs[key] = Programs(
                cfg, model, jit("prefill"), jit("prefill_last"), jit("prefill_extend_step"),
                jit("prefill_extend_group"), jit("decode_step"))
        return self._programs[key]

    @functools.cached_property
    def prompts(self) -> list:
        return [np.asarray(self.toks[row, a:b]) for row, a, b in self.case.prompts]

    def generated(self, prompt, n: int):
        """``generate()``'s greedy answer for the prompt alone, XLA backend."""
        out = generate(self.model, self.params, jnp.asarray(prompt)[None], n, GREEDY)
        return np.asarray(out)[0, -n:]

    @functools.cached_property
    def alone(self) -> list:
        return [self.generated(p, self.case.max_new) for p in self.prompts]

    def serve(self, backend: str, donate: bool, prompts=None, before=None, after=None,
              **over) -> Run:
        """The prompts through one ``SlotEngine``, resident together."""
        prog = self.programs(backend, **over)
        engine = SlotEngine(prog.model, self.params, **self.case.engine)
        engine.donate_carry = donate
        prompts = self.prompts if prompts is None else prompts
        for i, p in enumerate(prompts):
            engine.admit(DecodeRequest(prompt=p, max_new_tokens=self.case.max_new,
                                       sample=GREEDY, seed=i), tag=i)
        done, seen, counted = {}, [], []
        while engine.busy:
            if before is not None:
                seen.append(before(engine))
            for tag, res in engine.step():
                assert res.status == "ok", res.status
                done[tag] = np.asarray(res.tokens).reshape(-1)
            if after is not None:
                counted.append(after(engine))
        return Run([done[i] for i in range(len(prompts))], seen, counted, engine, prog.cfg)


def served_fixture(case: ServedCase):
    """The module's ``served`` fixture (``tests/conftest.py`` drops a module's
    compiled programs at its end: ROADMAP C13)."""

    @pytest.fixture(scope="module")
    def served():
        return Served(case)

    return served


def states_close(got, want, rows=None, **tol):
    """Every leaf of two lists of layer states; with ``rows``, K and V of a
    ``[B, KV, rows, Dh]`` cache up to the rows written."""
    for layer, (g, w) in enumerate(zip(got, want)):
        for name in w:
            a, b = np.asarray(g[name]), np.asarray(w[name])
            if rows is not None and name in ("k", "v"):
                a, b = a[:, :, :rows], b[:, :, :rows]
            np.testing.assert_allclose(a, b, err_msg=f"{layer}.{name}", **tol)


@functools.lru_cache(maxsize=None)
def _moe_program(cfg):
    return jax.jit(lambda p, x, live: MoEMLP(cfg).apply(
        {"params": p}, x, live, mutable=["moe_stats"]))


def moe_apply(cfg, p, x, live=None):
    """One expert layer -> (its output, what it sowed); one program a ``cfg``."""
    return _moe_program(cfg)(p, x, live)


def moe_stats(sown) -> dict:
    return dict(zip(STAT_NAMES, (int(v) for v in stats_vector(sown.get("moe_stats", {})))))


def rehearse_cell(cell: Cell, cache_dir, expect):
    """``benchmark/run.py --rehearse`` of one cell, end to end in a process
    of its own: exit 0, a last line that says ``correct`` with nothing
    failed; ``expect(result, lines)`` holds the cell's own readings."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    out = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell.name, "--seed", str(cell.seed),
         "--seconds", str(cell.seconds), "--trace", str(cell.trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1200)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [json.loads(line) for line in out.stdout.splitlines() if line.startswith("{")]
    result = lines[-1]
    assert result["correct"] and result["failed"] == 0
    expect(result, lines)


def trace_pins(case: ServedCase, programs) -> dict:
    """sha256 (first 16 hex) of the jaxpr text of the case's tiny model's
    programs, by shapes alone: the training forward, a padded whole-prompt
    prefill, a prompt piece and a decode step handed ``live``, each with the
    mixture's counters sown. A file pins its OWN preset's: a PR that adds a
    field to ``ModelConfig`` and changes what another preset traces fails
    that preset's file, and a PR that changes a program on purpose reads
    the new value from the assertion and says why beside it."""
    cfg = tiny_cfg(case)
    model = TransformerLM(cfg)
    toks = jnp.zeros((2, 48), jnp.int32)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), toks))
    live = jnp.ones((2,), bool)

    def states():  # a train-only preset has none
        return jax.eval_shape(lambda: init_decode_state(cfg, 2, jnp.float32))

    traced = {
        "forward": lambda: jax.make_jaxpr(lambda p, x: model.apply(
            p, x, mutable=["losses", "moe_stats"]))(params, toks),
        "prefill": lambda: jax.make_jaxpr(lambda p, x: model.apply(
            p, x, jnp.int32(40), method=model.prefill_last, mutable=["moe_stats"]))(params, toks),
        "piece": lambda: jax.make_jaxpr(lambda p, x, st: model.apply(
            p, x[:, :16], st, jnp.int32(16), jnp.int32(9), method=model.prefill_extend_step,
            mutable=["moe_stats"]))(params, toks, states()),
        "step": lambda: jax.make_jaxpr(lambda p, x, st: model.apply(
            p, x[:, 0], st, jnp.full((2,), 5, jnp.int32), None, live, method=model.decode_step,
            mutable=["moe_stats"]))(params, toks, states()),
    }
    return {name: hashlib.sha256(str(traced[name]()).encode()).hexdigest()[:16]
            for name in programs}


# -- the contract ------------------------------------------------------------------


class ServedContract:
    """The tests every served configuration takes; ``case`` says which and
    with what. The ``served`` fixture is the file's (``served_fixture``)."""

    case: ServedCase

    # a contract test -> the field of the case without which it does not apply
    APPLIES = {
        "test_prefill_equals_pieces_equals_the_decode_walk": "walk",
        "test_decode_step_with_a_row_list_touches_no_other_row": "row_list",
        "test_a_scan_that_holds_the_cache_walks_as_one_that_carries_it": "scan",
        "test_share_sum_of_all_chips_equals_the_uncut_layer": "share",
        "test_engine_serves_as_generate": "engines",
        "test_server_answers_as_generate": "server",
        "test_cell_rehearses_on_the_cpu": "cell",
        "test_traces_the_pinned_programs": "pins",
    }

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        for test, field in cls.APPLIES.items():
            if not getattr(cls.case, field) and test not in vars(cls):
                setattr(cls, test, None)  # not collected

    @classmethod
    def parametrise(cls, metafunc):
        """``pytest_generate_tests`` for a contract's methods: the case's
        backends and engines (an argument a method parametrises itself is
        left alone)."""
        case, test = cls.case, metafunc.function.__name__
        marked = {name.strip() for mark in metafunc.definition.iter_markers("parametrize")
                  for name in (mark.args[0].split(",") if isinstance(mark.args[0], str) else mark.args[0])}
        wanted = set(metafunc.fixturenames) - marked
        if "engine" in wanted:
            engines = [(*e, {})[:3] for e in case.engines]
            metafunc.parametrize("engine", engines, ids=[
                "-".join(map(str, (backend, donate, *over.values()))) for backend, donate, over in engines])
        if "program" in wanted:
            metafunc.parametrize("program", sorted(case.pins))
        if "backend" in wanted:
            backends = {"test_model_matches_the_reference": case.forward,
                        "test_prefill_equals_pieces_equals_the_decode_walk": case.walk and case.walk.backends,
                        }.get(test) or BACKENDS
            metafunc.parametrize("backend", backends)

    # -- what a configuration says of itself --------------------------------------

    def published(self, cfg: ModelConfig):
        """The preset's published widths, pattern, state shapes and counts."""
        raise NotImplementedError

    def before_boundary(self, engine):
        """A reading of the engine before each boundary -> ``Run.seen``."""

    def after_boundary(self, engine):
        """A reading after each boundary -> ``Run.counted``."""

    def after_engine(self, served: Served, run: Run, backend: str, donate: bool):
        """The counters and held bytes the engine must show."""

    def after_server(self, served: Served, counters: dict, prompts: list):
        pass

    def after_cell(self, result: dict, lines: list):
        pass

    def after_scan(self, start, merged):
        pass

    def share_layer(self, served: Served, spec: dict, p, x):
        """The reference's expert layer, shared expert and all."""
        raise NotImplementedError

    def after_share(self, served: Served, whole, p, x, want, stats: list):
        pass

    # -- the tests ----------------------------------------------------------------

    def test_preset_is_the_published_shape(self):
        cfg = get_config(self.case.name)
        for key, value in config_file(self.case.name)["model"].items():
            if key != "rehearse":  # the model as run is the preset
                assert _same(getattr(cfg, key), value), key
        self.published(cfg)

    def test_model_matches_the_reference(self, served, backend):
        """Logits of the whole forward, seeded weights."""
        case = self.case
        got = served.got
        if backend != "xla":
            with jax.default_matmul_precision("highest"):
                got = served.programs(backend).model.apply(served.params, served.toks)
        tol = pick(case.forward_tol, backend) or case.logit_tol
        if case.floor is not None:
            assert float(jnp.abs(served.want).max()) > case.floor
        np.testing.assert_allclose(got, served.want, atol=tol, rtol=0)
        if case.bites is not None and backend == "xla":
            other = served.reference(served.spec(**case.bites))
            assert float(jnp.abs(other - served.want).max()) > 100 * case.logit_tol

    def test_prefill_equals_pieces_equals_the_decode_walk(self, served, backend):
        """``prefill`` = pieces of ``prefill_extend`` (the last one padded: it
        stops at its ``length``) = ``decode_step`` token by token, from the
        prompt's state and from nothing: the logits against ONE full forward,
        and the states where the case gives a tolerance."""
        walk, params, toks = self.case.walk, served.params, served.toks
        prog = served.programs(backend)
        full = served.want if walk.against == "reference" else served.got
        tol = walk.tol or self.case.logit_tol
        n, b = pick(walk.n, backend), toks.shape[0]
        rows = n if walk.cache_rows else None
        close = functools.partial(np.testing.assert_allclose, atol=tol, rtol=0)
        logits, start = prog.prefill(params, toks[:, :n])
        close(logits, full[:, :n])
        left = {"prefill": start}
        if walk.padded:  # padded whole: the state stops at the real length
            padded = jnp.pad(toks[:, :n], ((0, 0), (0, walk.padded)))
            last, left["padded"] = prog.prefill_last(params, padded, jnp.int32(n))
            close(last, full[:, n - 1])
            states_close(left["padded"], start, n, atol=1e-5)
        st = init_decode_state(prog.cfg, b)
        for off in range(0, n, walk.piece):
            real = min(walk.piece, n - off)
            x = jnp.pad(toks[:, off:off + real], ((0, 0), (0, walk.piece - real)))
            last, st = prog.piece(params, x, st, jnp.int32(off), jnp.int32(real))
            close(last, full[:, off + real - 1])
        left["pieces"] = st
        if pick(walk.states, backend) is not None:
            states_close(st, start, rows, **pick(walk.states, backend))
        listed = dispatch.decode_live_rows(jnp.ones((b,), bool), backend=backend)
        states, live = left[walk.steps_from], jnp.ones((b,), bool) if walk.live else None
        for t in range(n, n + walk.steps):
            out, states = prog.step(
                params, toks[:, t], states, jnp.full((b,), t, jnp.int32), listed, live)
            close(out, full[:, t], err_msg=str(t))
        st = init_decode_state(prog.cfg, b)
        for t in range(walk.cold):
            out, st = prog.step(params, toks[:, t], st, jnp.int32(t))
            close(out, full[:, t], err_msg=str(t))
        if pick(walk.cold_states, backend) is not None:
            want = start if walk.cold == n else prog.prefill(params, toks[:, :walk.cold])[1]
            states_close(st, want, walk.cold if walk.cache_rows else None,
                         **pick(walk.cold_states, backend))

    def test_decode_step_with_a_row_list_touches_no_other_row(self, served):
        """Under the kernels, a slot-multiplexed decode step leaves every
        state leaf of an unlisted row bitwise untouched, and moves a listed
        row's."""
        prog, n = served.programs("pallas_interpret"), self.case.row_list
        _, states = prog.prefill(served.params, served.toks[:, :n])
        states = jax.tree.map(lambda x: jnp.concatenate([x, x[:1] + 1], axis=0), states)  # 3 rows
        rows = dispatch.decode_live_rows(jnp.array([True, False, True]), backend="pallas_interpret")
        _, new = prog.step(served.params, jnp.array([5, 6, 7]), states, jnp.full((3,), n), rows)
        for old, now in zip(jax.tree.leaves(states), jax.tree.leaves(new)):
            assert bool((now[1] == old[1]).all())
            assert not bool((now[0] == old[0]).all())

    def test_a_scan_that_holds_the_cache_walks_as_one_that_carries_it(self, served, backend):
        """``chunk_split`` / ``chunk_merge``: decode steps over the cache held
        read-only, with the chunk's own rows and the positions it started at
        carried, give the plain walk's logits and, merged, its state; with a
        row list the sequence it leaves out keeps every bit. A program that
        returns a new carry carries everything."""
        scan, params, toks = self.case.scan, served.params, served.toks
        prog = served.programs(backend)
        cfg, kinds = prog.cfg, prog.cfg.resolved_layer_types
        n, steps = scan.n, scan.steps
        _, start = prog.prefill(params, toks[:, :n])
        at0 = jnp.full((2,), n)
        lt = kinds[scan.layer]
        whole = MIXERS[lt].chunk_split(cfg, lt, start[scan.layer], steps, at0, False)
        assert whole[0] == {} and whole[1] is start[scan.layer]
        for mask in ([True, True], [True, False]):
            live = jnp.array(mask)
            rows = dispatch.decode_live_rows(live, backend=backend)
            if rows is None and not all(mask):
                continue  # without a list the decode programs freeze rows themselves
            split = [MIXERS[lt].chunk_split(cfg, lt, st, steps, at0, True)
                     for lt, st in zip(kinds, start)]
            held, carried, plain = [h for h, _ in split], [c for _, c in split], start
            assert set(held[scan.layer]) == set(scan.held)
            assert set(carried[scan.layer]) == set(scan.carried)
            for t in range(n, n + steps):
                at = jnp.where(live, t, n)  # a sequence that is not emitting holds its position
                want, plain = prog.step(params, toks[:, t], plain, at, rows)
                out, new = prog.step(params, toks[:, t], [{**h, **c} for h, c in zip(held, carried)],
                                     at, rows)
                carried = [{name: st[name] for name in c} for st, c in zip(new, carried)]
                np.testing.assert_allclose(out[live], want[live], atol=self.case.logit_tol, rtol=0)
            merged = [MIXERS[lt].chunk_merge(cfg, lt, h, c, live)
                      for lt, h, c in zip(kinds, held, carried)]
            for got, want, old in zip(*(jax.tree.leaves(x) for x in (merged, plain, start))):
                np.testing.assert_allclose(got[live], want[live], atol=2e-5, rtol=2e-5)
                assert bool((got[~live] == old[~live]).all())
            self.after_scan(start, merged)

    def test_share_sum_of_all_chips_equals_the_uncut_layer(self, served, backend):
        """16 experts over 4 chips, 4 held each: every share is the
        reference's GIVEN the same share, the routed parts of the 4 shares
        plus the shared expert ONCE are the uncut reference layer, every
        routed pair has one owner and nothing drops."""
        share, cfg = self.case.share, served.cfg
        whole, p = served.uncut_layer
        x = jax.random.normal(jax.random.key(2), (2, 40, cfg.d_model))
        live = jnp.ones((2, 40), bool)
        with jax.default_matmul_precision("highest"):
            want = self.share_layer(served, served.spec(whole), p, x)
            shared = served.ref.shared_expert(served.spec(whole), p, x)
        total, stats = jnp.zeros_like(x), []
        for at in range(0, 16, 4):
            mine_cfg = dataclasses.replace(cfg, moe_expert_offset=at, backend=backend)
            mine = {**p, **{name: p[name][at:at + 4] for name in share.experts}}
            got, sown = moe_apply(mine_cfg, mine, x, live)
            stats.append(moe_stats(sown))
            assert stats[-1]["dropless_overflow"] == 0
            assert stats[-1]["rows_routed"] == 2 * 40 * cfg.moe_top_k
            with jax.default_matmul_precision("highest"):
                part = self.share_layer(served, served.spec(mine_cfg), mine, x)
            assert float(jnp.abs(got - part).max()) < share.part_tol
            total = total + (got - shared)
        assert sum(s["rows_held"] for s in stats) == 2 * 40 * cfg.moe_top_k
        assert float(jnp.abs(total + shared - want).max()) < share.sum_tol
        self.after_share(served, whole, p, x, want, stats)

    def test_engine_serves_as_generate(self, served, engine):
        """Through ``SlotEngine``: the case's three requests resident
        together, pieces and decode interleaved, under the engine's backend
        with its carry donated or not; each request's ids are ``generate()``'s
        for it alone on the XLA backend (computed once a file) and, where the
        case gives a gap, teacher-forced through the reference's ONE full
        forward each served id is the reference's own choice to that gap.
        What the engine counted and holds: ``after_engine``."""
        backend, donate, over = engine
        run = served.serve(backend, donate, before=self.before_boundary,
                           after=self.after_boundary, **over)
        for ids, alone in zip(run.ids, served.alone):
            np.testing.assert_array_equal(ids, alone)
        if self.case.served_gap is not None:
            for p, ids in zip(served.prompts, run.ids):
                logits = served.forced(p, ids)
                mine = jnp.take_along_axis(logits, jnp.asarray(ids)[:, None], axis=-1)[:, 0]
                assert float((logits.max(-1) - mine).max()) <= self.case.served_gap
        self.after_engine(served, run, backend, donate)

    def test_server_answers_as_generate(self, served):
        """The ``Server`` over the tiny model: 4 slots, five requests, pieces
        and decode interleaved; every answer is ``generate()``'s."""
        setup = self.case.engine
        srv = Server(served.model, served.params, ServeConfig(
            chunk=setup["chunk"], slots=setup["slots"], max_inflight=8,
            prefill_chunk=setup["prefill_chunk"],
            prefill_buckets=",".join(map(str, setup["prefill_buckets"])), cost=False))
        prompts = [np.asarray(served.toks[i % 2, a:b]) for i, (a, b) in
                   enumerate([(0, 100), (0, 20), (50, 200), (10, 75), (3, 150)])]
        handles = [srv.submit(DecodeRequest(prompt=p, max_new_tokens=7, sample=GREEDY, seed=i))
                   for i, p in enumerate(prompts)]
        srv.serve(drain_when_idle=True)
        counters = srv.metrics.counters_flat()
        srv.close()
        for p, h in zip(prompts, handles):
            assert h.result.status == "ok"
            np.testing.assert_array_equal(
                np.asarray(h.result.tokens).reshape(-1), served.generated(p, 7))
        self.after_server(served, counters, prompts)

    def test_cell_rehearses_on_the_cpu(self, tmp_path):
        """The configuration's cell end to end at tiny sizes: the served
        kind, the reference named by the configuration's file, the check on
        what was served in the window, the cell's own metrics."""
        rehearse_cell(self.case.cell, tmp_path / "cache", self.after_cell)

    def test_traces_the_pinned_programs(self, program):
        assert trace_pins(self.case, (program,))[program] == self.case.pins[program]
