"""Trainer: jitted sharded train step, AdamW/Lion, warmup+cosine schedules,
grad accumulation, bf16 policy, NaN/Inf guard, eval loop (SURVEY.md T2/T3/
T7/A2).

The reference's torch training loop + NCCL DDP wrapper (BASELINE.json;
reference checkout never mounted — SURVEY.md §0) becomes: one TrainState
pytree sharded over the (dp, fsdp, tp, sp) mesh by path-based rules
(parallel/sharding.py — the same rules cover optimizer moments, whose tree
paths end in the param path), and one jitted step function; GSPMD inserts
every collective. Mixed precision is structural: params fp32, activations
bf16 (model cfg.dtype), logits + loss + grads fp32 master.

Failure detection (A2): each step computes finite = isfinite(loss) &
isfinite(grad_norm); on a bad step the update is skipped tree-wide
(params/opt state keep their old values). A cumulative skip counter is
carried device-side in TrainState, so the host reads it only at log
cadence yet no bad step between log points is missed; ``nan_policy="halt"``
raises at the next log point if the counter advanced.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from orion_tpu.models.configs import ModelConfig
from orion_tpu.models.transformer import TransformerLM, _dtype
from orion_tpu.obs import flight as _flight
from orion_tpu.parallel.mesh import MeshConfig, make_mesh
from orion_tpu.parallel.sharding import batch_sharding, param_shardings
from orion_tpu.resilience import inject as _inject
from orion_tpu.utils import rng as rngs
from orion_tpu.obs.trace import NULL_SPAN, PROCESS_TRACER, compile_totals
from orion_tpu.utils.profiling import annotate

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    model: ModelConfig = ModelConfig()
    steps: int = 1000
    batch_size: int = 8  # global
    seq_len: int = 256
    # optimizer
    optimizer: str = "adamw"  # "adamw" | "lion" | "adafactor"
    mu_dtype: Optional[str] = None  # e.g. "bfloat16": halve first-moment HBM
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    accum_steps: int = 1
    # schedule
    schedule: str = "cosine"  # "cosine" | "linear" | "constant"
    warmup_steps: int = 100
    min_lr_ratio: float = 0.1
    # parallelism
    mesh: MeshConfig = MeshConfig()
    # pipeline parallelism (mesh.pp > 1): number of GPipe microbatches;
    # 0 = auto (4*pp capped at batch_size). Bubble = (pp-1)/(n_micro+pp-1).
    pp_microbatches: int = 0
    # None = auto (parallel/pipeline_lm.py: real-Mosaic backend on a
    # tp==ep==1, fsdp==1 mesh); True forces the fully-manual pipeline
    # (Mosaic kernels inside pp, batch explicit on dp/fsdp — with fsdp>1
    # this trades ZeRO memory for kernels); False forces partial-manual
    pp_full_manual: Optional[bool] = None
    # parameter storage (VERDICT r4 #1): "float32" keeps the classic fp32
    # master weights. "bfloat16_sr" stores every matrix param bf16 and
    # applies updates with STOCHASTIC ROUNDING — no master copy at all, on
    # device or host. On the 16GB chip this halves both the persistent
    # param bytes AND the grad buffer (grads adopt the leaf dtype), ~5.3GB
    # back at 1.3B — bought as un-rematted blocks (remat_skip). A
    # host-offloaded fp32 master was rejected: every step would round-trip
    # 5.3GB over the host link.
    # Rounding is unbiased (E[sr(x)] = x, tests/test_training.py), so the
    # tiny-update-vs-0.4%-ulp problem deterministic bf16 rounding has
    # disappears in expectation; 1D leaves (norm scales, biases) stay fp32
    # (<0.1% of bytes, and their updates are the most precision-critical).
    param_storage: str = "float32"  # "float32" | "bfloat16_sr"
    # bookkeeping
    seed: int = 0
    log_every: int = 10
    eval_every: int = 0
    eval_batches: int = 8
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 1000
    ckpt_keep: int = 3
    nan_policy: str = "skip"  # "skip" | "halt"
    # resilience (resilience/): preempt_grace > 0 installs SIGTERM/SIGINT
    # handlers around train() — first signal = graceful stop at the next
    # step boundary + emergency checkpoint, second = die now; the value is
    # the seconds budgeted for that emergency save. step_timeout > 0 arms
    # a hang watchdog AND the data-loader stall detector: no step heartbeat
    # (or no batch) for that long raises StallError instead of hanging.
    # Must comfortably exceed jit compile + one step, not just one step.
    preempt_grace: float = 10.0
    step_timeout: float = 0.0

    @property
    def micro_batch(self) -> int:
        assert self.batch_size % self.accum_steps == 0
        return self.batch_size // self.accum_steps


class TrainState(struct.PyTreeNode):
    step: Array
    params: Any
    opt_state: Any
    rng: Array
    # cumulative count of skipped non-finite steps, carried device-side so the
    # host only reads it at log cadence yet no bad step is ever missed (A2)
    nonfinite: Array


def make_schedule(cfg: TrainConfig):
    peak, warm = cfg.lr, max(cfg.warmup_steps, 1)
    floor = cfg.lr * cfg.min_lr_ratio
    decay_steps = max(cfg.steps - warm, 1)
    if cfg.schedule == "cosine":
        return optax.warmup_cosine_decay_schedule(
            0.0, peak, warm, warm + decay_steps, end_value=floor
        )
    if cfg.schedule == "linear":
        return optax.join_schedules(
            [
                optax.linear_schedule(0.0, peak, warm),
                optax.linear_schedule(peak, floor, decay_steps),
            ],
            [warm],
        )
    return optax.join_schedules(
        [optax.linear_schedule(0.0, peak, warm), optax.constant_schedule(peak)],
        [warm],
    )


def _sr_noise_bits(key: Array, n: int) -> Array:
    """n uniform uint32 words from a counter hash: Weyl-sequenced iota
    through the murmur3 finalizer, salted by the two PRNG key words. SR
    needs uniform noise, not cryptographic noise — threefry was the costlier
    generator (not measured on the current installation), and the noise
    only has to make E[low 16 bits] uniform (distribution-tested)."""
    kd = key
    if jnp.issubdtype(kd.dtype, jax.dtypes.prng_key):
        kd = jax.random.key_data(key)
    kd = kd.reshape(-1).astype(jnp.uint32)
    h = jax.lax.iota(jnp.uint32, n) * jnp.uint32(0x9E3779B9) + kd[0]
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B) ^ kd[-1]
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def sr_round_bf16(x32: Array, key: Array) -> Array:
    """Stochastically round fp32 -> bf16, unbiased: E[sr(x)] == x exactly.

    bf16 is the top 16 bits of the fp32 pattern, so the two bf16 neighbors
    of x are truncate(x) and the next representable magnitude; adding
    uniform 16-bit noise (counter-hash — _sr_noise_bits) to the truncated
    bits and then truncating selects the far neighbor with probability
    (low_bits / 2^16) — the textbook integer-SR construction, exact for
    either sign because IEEE bit patterns order by magnitude within a
    sign. A value already representable in bf16 (low bits zero) is
    returned bit-identically, so a zero update cannot perturb params.
    Non-finite inputs bypass the add (noise on an inf pattern would
    fabricate a NaN payload)."""
    bits = jax.lax.bitcast_convert_type(x32, jnp.uint32)
    r = _sr_noise_bits(key, x32.size).reshape(x32.shape) & jnp.uint32(0xFFFF)
    sr = jax.lax.bitcast_convert_type(
        ((bits + r) >> 16).astype(jnp.uint16), jnp.bfloat16
    )
    return jnp.where(jnp.isfinite(x32), sr, x32.astype(jnp.bfloat16))


def storage_cast(params: Any, param_storage: str) -> Any:
    """Apply the TrainConfig.param_storage policy to a fresh param tree:
    "bfloat16_sr" stores matrix (ndim>=2) fp32 leaves as bf16; 1D leaves
    (norm scales, biases — <0.1% of bytes, most precision-sensitive) stay
    fp32."""
    if param_storage == "float32":
        return params
    assert param_storage == "bfloat16_sr", param_storage
    return jax.tree.map(
        lambda p: (
            p.astype(jnp.bfloat16)
            if p.ndim >= 2 and p.dtype == jnp.float32
            else p
        ),
        params,
    )


def _wd_mask(params: Any) -> Any:
    """Decay only matrix params; skip norms/biases/scalars and the fixed
    FAVOR+ projection (its grads are stop_gradient'd — decay would shrink
    it to zero)."""

    def mask(path, leaf):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        # pipeline layout stacks a leading layer axis: a stacked norm scale
        # is [L, d] — still "not a matrix" per-layer, so shift the threshold
        min_ndim = 3 if "blocks_stacked" in name else 2
        return leaf.ndim >= min_ndim and "favor_proj" not in name

    return jax.tree_util.tree_map_with_path(mask, params)


def make_optimizer(
    cfg: TrainConfig, include_clip: bool = True
) -> optax.GradientTransformation:
    """``include_clip=False``: the caller folds global-norm clipping into
    its own gradient pass (Trainer._train_step fuses it with the finite
    guard and the metrics norm — one norm reduction instead of two and one
    elementwise scale instead of two, measured ~half the optimizer-side
    reduce-fusion time at 1.3B; BASELINE.md train-step profile)."""
    sched = make_schedule(cfg)
    mu_dtype = cfg.mu_dtype
    if cfg.optimizer == "adamw":
        opt = optax.adamw(
            sched, b1=cfg.b1, b2=cfg.b2, eps=cfg.eps,
            weight_decay=cfg.weight_decay, mask=_wd_mask, mu_dtype=mu_dtype,
        )
    elif cfg.optimizer == "lion":
        opt = optax.lion(
            sched, b1=cfg.b1, b2=cfg.b2,
            weight_decay=cfg.weight_decay, mask=_wd_mask, mu_dtype=mu_dtype,
        )
    elif cfg.optimizer == "adafactor":
        # factored second moment (O(n+m) state per matrix): the single-chip
        # memory-headroom option for 1.3B+ (SURVEY §7 "bigger-batch").
        # No decoupled weight decay — standard adafactor usage; its
        # update-clipping plays the stabilizing role.
        opt = optax.adafactor(
            sched, min_dim_size_to_factor=128,
            multiply_by_parameter_scale=False,
        )
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    chain = [opt]
    if cfg.clip_norm and cfg.clip_norm > 0:
        # include_clip=False keeps an identity placeholder where the clip
        # transform sat: both have EmptyState, so the opt_state pytree (and
        # therefore every existing orbax checkpoint) is structurally
        # unchanged by the caller-side clip fusion
        head = (
            optax.clip_by_global_norm(cfg.clip_norm)
            if include_clip
            else optax.identity()
        )
        chain.insert(0, head)
    return optax.chain(*chain)


from orion_tpu.ops.fused_ce import fused_ce_ok as _fused_ce_ok  # shared gate


# name sown into "moe_stats" (models/moe.py) -> step metric
MOE_STATS = {
    "dropless_overflow": "moe_overflow",
    "rows_routed": "moe_rows_routed",
    "rows_held": "moe_rows_held",
    "rows_max_expert": "moe_rows_max_expert",
    "tiles_live": "moe_tiles_live",
}


def zero_moe_stats() -> Dict[str, Array]:
    return {name: jnp.zeros((), jnp.int32) for name in MOE_STATS.values()}


def lm_loss(
    model: TransformerLM, params, batch: Array, dropout_rng=None,
    fused_ce: Optional[bool] = None, return_stats: bool = False,
):
    """batch [B, T+1] -> mean next-token cross entropy (fp32), plus any
    auxiliary losses modules sowed into the "losses" collection (MoE
    load-balance + z-loss, models/moe.py — already weighted there).

    ``fused_ce``: None = auto (_fused_ce_ok); the fused path computes the
    identical loss without materializing [B, T, V] fp32 logits.

    ``return_stats``: also return a fixed-structure diagnostics dict, the
    "moe_stats" collection summed over layers BY NAME (``MOE_STATS``):
    ``moe_overflow`` (rows dropped past a static budget,
    models/moe.py::_dropless_ep / _dropless_held) and the held-experts
    layer's row counters ``moe_rows_routed`` / ``moe_rows_held`` /
    ``moe_rows_max_expert`` and ``moe_tiles_live``, the row tiles its grouped
    product visits; 0 whenever nothing sowed. The structure is
    static so it can ride a grad-accumulation scan carry (ADVICE r4: the
    counter existed but had no consumer — "counted, never silent" requires
    a reader)."""
    x, y = batch[:, :-1], batch[:, 1:]
    kwargs = {}
    if dropout_rng is not None:
        kwargs = {"rngs": {"dropout": dropout_rng}, "deterministic": False}
    if fused_ce is None:
        fused_ce = _fused_ce_ok(model)
    if fused_ce:
        from orion_tpu.ops.fused_ce import model_token_losses

        losses, variables = model_token_losses(
            model, params, x, y, mutable=True, **kwargs
        )
    else:
        logits, variables = model.apply(
            params, x, mutable=["losses", "moe_stats"], **kwargs
        )
        losses = optax.softmax_cross_entropy_with_integer_labels(logits, y)
    loss = losses.mean()
    for leaf in jax.tree.leaves(variables.get("losses", {})):
        loss = loss + leaf
    if not return_stats:
        return loss
    stats = zero_moe_stats()
    for path, leaf in jax.tree_util.tree_leaves_with_path(
        variables.get("moe_stats", {})
    ):
        sown = next(
            p.key for p in reversed(path) if isinstance(p, jax.tree_util.DictKey)
        )
        stats[MOE_STATS[sown]] = stats[MOE_STATS[sown]] + leaf.astype(jnp.int32)
    return loss, stats


class Trainer:
    def __init__(
        self,
        cfg: TrainConfig,
        mesh: Optional[Mesh] = None,
        materialize: bool = True,
        tracer=None,
    ):
        """``materialize=False`` builds the mesh, shardings, and jitted step
        WITHOUT allocating params/optimizer state — the AOT planning path
        (orion_tpu/aot.py): a 7B step can be lowered and compiled on a
        virtual CPU mesh whose host could never hold the weights.

        ``tracer`` (obs/trace.py ``Tracer``, as ``Server`` takes one)
        writes the set-up tree: ``setup.trainer`` around this constructor
        with ``setup.init_state`` inside, ``setup.restore``, and in
        :meth:`train` ``setup.loader`` / ``.first_step`` / ``.first_eval``
        / ``.ready``; the process's own tracer where none is handed in.
        They reach the process-wide record either way."""
        self.trace = tracer if tracer is not None else PROCESS_TRACER
        # the once-a-Trainer set-up spans already written, and the first
        # step's loss until it is back
        self._setup_done: set = set()
        self._first_loss = None
        with self.trace.span("setup.trainer", "setup"):
            self._build(cfg, mesh, materialize)

    def _build(self, cfg: TrainConfig, mesh, materialize: bool) -> None:
        # fail loudly: out-of-range positions would be silently clamped by
        # XLA gather, yielding wrong position embeddings (train.py's CLI
        # auto-bumps max_seq_len; the library path must not rely on that)
        if cfg.seq_len > cfg.model.max_seq_len:
            raise ValueError(
                f"seq_len={cfg.seq_len} exceeds model.max_seq_len="
                f"{cfg.model.max_seq_len}; raise max_seq_len or lower seq_len"
            )
        self.cfg = cfg
        self.mesh = mesh if mesh is not None else make_mesh(cfg.mesh)
        m = cfg.model
        ep = self.mesh.shape.get("ep", 1)
        if (
            m.n_experts and m.moe_dropless and ep > 1
            and (m.moe_ep_buffer < ep or self.mesh.shape.get("pp", 1) > 1)
        ):
            # moe_ep_buffer >= ep is mathematically dropless
            # (models/moe.py::_dropless_ep); below that an extremely
            # imbalanced router can drop rows past a shard's budget. The
            # counter surfaces in step metrics ("moe_overflow"), but warn
            # up front so the regime is chosen, not stumbled into. On pp
            # meshes the counter is NOT surfaced (pp_lm_loss doesn't
            # thread moe_stats out), so warn there even with ample buffer.
            import warnings

            pp_note = (
                " (and pp>1 does not surface the 'moe_overflow' metric)"
                if self.mesh.shape.get("pp", 1) > 1 else ""
            )
            warnings.warn(
                f"moe_ep_buffer={m.moe_ep_buffer} with ep={ep}: dropless-ep "
                "is only budget-dropless below moe_ep_buffer>=ep; watch the "
                f"'moe_overflow' step metric{pp_note}, or set "
                f"moe_ep_buffer>={ep} for the guarantee",
                stacklevel=2,
            )
        # mesh is always passed: the model uses it for activation sharding
        # constraints; the sp attention path additionally gates on
        # cfg.sequence_parallel and mesh sp-axis size > 1
        self.model = TransformerLM(cfg.model, mesh=self.mesh)
        # remat_skip's memory budget assumes the fused-CE loss freed the
        # fp32-logits temp (configs.py LM_1B3). Paths that keep the unfused
        # head — pp (pp_lm_loss builds its own stacked pipeline; remat_skip
        # is meaningless there anyway) and quantized models (_fused_ce_ok)
        # — get the skip zeroed so they never pay un-rematted activations
        # AND full logits. sp meshes now ride the fused path
        # (ops/fused_ce.py::_sp_fused_ce) and keep their skip.
        if cfg.model.remat_skip and (
            self.mesh.shape.get("pp", 1) > 1 or not _fused_ce_ok(self.model)
        ):
            self.model = TransformerLM(
                dataclasses.replace(cfg.model, remat_skip=0), mesh=self.mesh
            )
        # pipeline parallelism: blocks run as a GPipe pipeline over the pp
        # axis and the state stores block params STACKED on a leading layer
        # axis sharded over pp (parallel/pipeline_lm.py)
        self.pp = self.mesh.shape.get("pp", 1)
        if self.pp > 1:
            from orion_tpu.parallel.pipeline_lm import stage_group

            g = stage_group(cfg.model)
            n_groups = cfg.model.n_layers // g
            assert n_groups % self.pp == 0, (
                f"pp={self.pp} must divide the {n_groups} stage groups "
                f"(layer pattern repeats with period {g} over "
                f"{cfg.model.n_layers} layers)"
            )
            # pp+sp composes: the pipeline shard_map is manual over both
            # axes and blocks run the sp-local attention bodies
            # (parallel/pipeline_lm.py); seq_len must shard evenly
            if cfg.model.sequence_parallel and self.mesh.shape.get("sp", 1) > 1:
                assert cfg.seq_len % self.mesh.shape["sp"] == 0, (
                    cfg.seq_len, dict(self.mesh.shape)
                )
            # the pipeline sees one accumulation micro-batch at a time, so
            # GPipe microbatches must divide cfg.micro_batch, not batch_size
            base = cfg.micro_batch
            # a full_manual pipeline shards the batch over dp·fsdp
            # EXPLICITLY, so n_micro must divide the PER-SHARD batch —
            # mirror pipeline_lm.py's auto rule (True, or None + a
            # real-Mosaic backend on a tp==ep==1, fsdp==1 mesh) so the
            # auto heuristic never picks a divisor the pipeline rejects
            from orion_tpu.ops.dispatch import resolve as _resolve

            fm = cfg.pp_full_manual
            if fm is None:
                fm = (
                    _resolve(cfg.model.backend) == "pallas"
                    and self.mesh.shape.get("tp", 1) == 1
                    and self.mesh.shape.get("ep", 1) == 1
                    and self.mesh.shape.get("fsdp", 1) == 1
                )
            if fm:
                base = base // (
                    self.mesh.shape.get("dp", 1)
                    * self.mesh.shape.get("fsdp", 1)
                )
            if cfg.pp_microbatches:
                self.pp_n_micro = cfg.pp_microbatches
            else:  # auto: largest divisor of base not exceeding 4*pp
                cap = max(1, min(base, 4 * self.pp))
                self.pp_n_micro = max(
                    d for d in range(1, cap + 1) if base % d == 0
                )
            assert base % self.pp_n_micro == 0, (
                f"pp_microbatches={self.pp_n_micro} must divide the "
                f"{'per-shard ' if fm else ''}per-accumulation batch {base}"
            )
        if cfg.param_storage not in ("float32", "bfloat16_sr"):
            raise ValueError(
                f"param_storage={cfg.param_storage!r}; expected 'float32' "
                "or 'bfloat16_sr'"
            )
        self._sr = cfg.param_storage == "bfloat16_sr"
        self.tx = make_optimizer(cfg, include_clip=False)
        self.sched = make_schedule(cfg)
        self.batch_shd = batch_sharding(self.mesh)

        root = rngs.root_key(cfg.seed)
        self._init_rng = rngs.stream(root, "init")
        self._dropout_rng = rngs.stream(root, "dropout")

        # init runs one forward for shape inference; its sample batch must
        # divide the data axes (the sp shard_map asserts divisibility)
        n_data = self.mesh.shape.get("dp", 1) * self.mesh.shape.get("fsdp", 1)
        sample_tokens = jnp.zeros((n_data, cfg.seq_len), jnp.int32)

        def init_fn(rng):
            params = self.model.init(rng, sample_tokens)
            if self.pp > 1:
                from orion_tpu.parallel.pipeline_lm import stack_lm_params

                params = stack_lm_params(self.model, params)
            params = storage_cast(params, cfg.param_storage)
            # optimizer stats adopt the dtype of the params they see
            # (probed: optax adafactor/adamw zeros_like the leaves) — init
            # from an fp32 view so bf16 STORAGE never degrades the fp32
            # STATE the update math runs in; the view is an init-time temp
            opt_view = jax.tree.map(
                lambda p: (
                    p.astype(jnp.float32) if p.dtype == jnp.bfloat16 else p
                ),
                params,
            )
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=self.tx.init(opt_view),
                rng=self._dropout_rng,
                nonfinite=jnp.zeros((), jnp.int32),
            )

        self._abstract = jax.eval_shape(init_fn, self._init_rng)
        # one rule set shards the whole state: optimizer-moment paths end in
        # the same 'wq/kernel'-style suffixes the param rules match on
        self.state_shardings = param_shardings(self._abstract, self.mesh)
        self.state = None
        if materialize:
            with self.trace.span("setup.init_state", "setup"):
                self.state = jax.block_until_ready(
                    jax.jit(init_fn, out_shardings=self.state_shardings)(
                        self._init_rng
                    )
                )

        self._step_fn = jax.jit(
            self._train_step,
            donate_argnums=(0,),
            in_shardings=(self.state_shardings, self.batch_shd),
            out_shardings=(self.state_shardings, None),
        )
        self._eval_fn = jax.jit(
            self._eval_step, in_shardings=(self.state_shardings.params, self.batch_shd)
        )
        self.nonfinite_steps = 0
        # step at which a graceful preemption stopped train(), else None
        self.preempted_at: Optional[int] = None

    # -- jitted bodies ------------------------------------------------------

    def _train_step(
        self, state: TrainState, batch: Array
    ) -> Tuple[TrainState, Dict[str, Array]]:
        cfg = self.cfg
        use_dropout = cfg.model.dropout > 0.0
        step_rng = rngs.at_step(state.rng, state.step)

        def loss_for(params, b, r):
            if self.pp > 1:
                from orion_tpu.parallel.pipeline_lm import pp_lm_loss

                return pp_lm_loss(
                    self.model, params, b, self.mesh,
                    n_micro=self.pp_n_micro,
                    dropout_rng=r if use_dropout else None,
                    full_manual=cfg.pp_full_manual,
                ), zero_moe_stats()
            return lm_loss(
                self.model, params, b, r if use_dropout else None,
                return_stats=True,
            )

        grad_fn = jax.value_and_grad(loss_for, has_aux=True)

        if cfg.accum_steps == 1:
            (loss, stats), grads = grad_fn(state.params, batch, step_rng)
        else:
            micro = batch.reshape(cfg.accum_steps, cfg.micro_batch, -1)

            def body(carry, mb_i):
                acc_loss, acc_stats, acc_grads, i = carry
                r = jax.random.fold_in(step_rng, i)
                (l, st), g = grad_fn(state.params, mb_i, r)
                acc = jax.tree.map(jnp.add, acc_grads, g)
                acc_stats = jax.tree.map(jnp.add, acc_stats, st)
                return (acc_loss + l, acc_stats, acc, i + 1), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            stats0 = zero_moe_stats()
            (loss, stats, grads, _), _ = jax.lax.scan(
                body,
                (jnp.zeros((), jnp.float32), stats0, zeros,
                 jnp.zeros((), jnp.int32)),
                micro,
            )
            loss = loss / cfg.accum_steps
            grads = jax.tree.map(lambda g: g / cfg.accum_steps, grads)

        if self._sr:
            # bf16-stored leaves yield bf16 grads (tangent dtype follows
            # the primal); the optimizer math runs fp32. No standalone
            # upcast pass: the converts fuse into the norm reduction here
            # and into the scale multiply below (a materialized f32 grads
            # copy is pure HBM traffic; its cost is not measured on the
            # current installation), and accumulation is f32 either way.
            gnorm = jnp.sqrt(sum(
                jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in jax.tree.leaves(grads)
            ))
        else:
            gnorm = optax.global_norm(grads)
        finite = jnp.isfinite(loss) & jnp.isfinite(gnorm)

        # ONE scalar folds clipping (optax.clip_by_global_norm semantics:
        # g * min(1, clip/||g||)) and the finite guard (zero grads on a bad
        # step) into a single fused elementwise pass over the grads, reusing
        # the metrics norm instead of a second reduction inside the chain
        clip = (
            jnp.minimum(1.0, cfg.clip_norm / gnorm)
            if cfg.clip_norm and cfg.clip_norm > 0
            else 1.0
        )
        # where (not *): a NaN gnorm must select 0, not propagate
        scale = jnp.where(finite, clip, 0.0)
        bad = (~finite).astype(jnp.int32)
        # astype is a no-op for the fp32 path; in SR mode it upcasts
        # the bf16 grads inside the same elementwise pass as the scale
        safe_grads = jax.tree.map(
            lambda g: g.astype(jnp.float32) * scale, grads
        )
        updates, new_opt = self.tx.update(
            safe_grads, state.opt_state, state.params
        )
        if self._sr:
            new_params = self._sr_apply(state.params, updates, step_rng)
        else:
            new_params = optax.apply_updates(state.params, updates)
        # skip-policy: on a non-finite step keep the old params & state
        sel = lambda new, old: jax.tree.map(  # noqa: E731
            lambda n, o: jnp.where(finite, n, o), new, old
        )
        new_params = sel(new_params, state.params)
        new_opt = sel(new_opt, state.opt_state)
        new_state = TrainState(
            step=state.step + 1,
            params=new_params,
            opt_state=new_opt,
            rng=state.rng,
            nonfinite=state.nonfinite + bad,
        )
        metrics = {
            "loss": loss,
            "grad_norm": gnorm,
            # the lr actually applied this step: the optimizer indexes
            # the schedule by the GOOD-step count (non-finite steps roll the
            # opt state — and with it the inner schedule count — back), and
            # that count is exactly step - nonfinite, so sched(state.step)
            # would permanently lead the applied lr after any skipped step
            "lr": self.sched(state.step - state.nonfinite),
            "nonfinite": bad,
            "nonfinite_total": new_state.nonfinite,
        }
        if cfg.model.n_experts and cfg.model.moe_dropless and self.pp == 1:
            # ADVICE r4: the dropless-ep overflow counter must have a
            # consumer — rows dropped past the static budget now surface
            # in every step's metrics (0 on non-ep meshes by construction).
            # pp meshes OMIT the key rather than report a hard-coded 0:
            # pp_lm_loss doesn't thread the moe_stats collection out, and
            # an absent metric says "not measured" where 0 would say "no
            # drops" (r5 review).
            metrics["moe_overflow"] = stats["moe_overflow"]
            if cfg.model.resolved_router_width != cfg.model.n_experts:
                # one chip's share of an expert-parallel layer: the rows
                # the router sent out, those whose expert is held here,
                # the busiest held expert's, and the row tiles the grouped
                # product visits of its buffer's (summed over layers)
                for name in ("moe_rows_routed", "moe_rows_held", "moe_rows_max_expert",
                             "moe_tiles_live"):
                    metrics[name] = stats[name]
        return new_state, metrics

    def _sr_apply(self, params, updates, step_rng: Array):
        """p + u with stochastic rounding on bf16-stored leaves (fp32
        leaves add exactly). Keys derive from the step rng (a fold_in'd
        stream independent of dropout) + the leaf's flatten index, so a
        resumed run replays the identical rounding — the bitwise-resume
        guarantee (A3) survives param_storage='bfloat16_sr'."""
        key = jax.random.fold_in(step_rng, 0x5157)
        leaves, treedef = jax.tree.flatten(params)
        ups = treedef.flatten_up_to(updates)
        out = []
        for i, (p, u) in enumerate(zip(leaves, ups)):
            if p.dtype == jnp.bfloat16:
                out.append(
                    sr_round_bf16(
                        p.astype(jnp.float32) + u,
                        jax.random.fold_in(key, i),
                    )
                )
            else:
                out.append((p + u).astype(p.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _eval_step(self, params, batch: Array) -> Tuple[Array, Array]:
        from orion_tpu.evaluate import lm_eval_sums  # single eval-loss defn

        logits_fn = None
        if self.pp > 1:
            from orion_tpu.parallel.pipeline_lm import pp_lm_logits

            logits_fn = lambda m, p, x: pp_lm_logits(  # noqa: E731
                m, p, x, self.mesh, n_micro=self.pp_n_micro
            )
        return lm_eval_sums(self.model, params, batch, logits_fn=logits_fn)

    # -- host API -----------------------------------------------------------

    def _once(self, name: str):
        """The set-up span ``name`` the first time a Trainer asks for it,
        the shared null span ever after."""
        if name in self._setup_done:
            return NULL_SPAN
        self._setup_done.add(name)
        return self.trace.span(name, "setup")

    @staticmethod
    def _show_compiles(registry) -> None:
        """The process's compile counters (obs/trace.py
        ``COMPILE_COUNTERS``) onto a logger's registry, at log cadence:
        what jax built since the registry last showed them."""
        for key, total in compile_totals().items():
            cell = registry.counter(key)
            cell.inc(total - cell.value())

    def _first_step(self, batch: Array, span) -> Dict[str, float]:
        """The step that builds the step program, inside its set-up span:
        the span also says what the rematted blocks' names policy kept while
        jax traced it (``models/transformer.py::REMAT_KEEPS``): the residuals
        a step holds from forward to backward and their MB, 0 and 0.0 where
        nothing engaged."""
        before = compile_totals()
        metrics = self.step(batch)
        after = compile_totals()
        kept, nbytes = (after[k] - before[k]
                        for k in ("remat_kept_residuals", "remat_kept_bytes"))
        span.note(remat_kept_residuals=int(kept), remat_kept_mb=round(nbytes / 1e6, 3))
        self._first_loss = metrics["loss"]
        return metrics

    def step(self, batch: Array) -> Dict[str, float]:
        assert self.state is not None, (
            "Trainer was built with materialize=False (AOT planning only); "
            "no state to train"
        )
        # chaos harness (resilience/inject.py): a NaN-poisoned step. One
        # leaf goes NaN -> non-finite loss/grads -> the device-side guard
        # skips the update tree-wide, so after the step params == the
        # pre-step values we stash here (copies: _step_fn donates its
        # input buffers). Net effect is exactly a transient NaN-grad step:
        # step+1, nonfinite+1, params/opt state unchanged.
        keep = None
        # gate on active() FIRST: int(state.step) reads a device scalar
        # (output of the previous jitted step), and an unconditional read
        # would host-sync every step — exactly the serialization the log-
        # cadence metric reads avoid
        if _inject.active() and _inject.nan_armed(int(self.state.step) + 1):
            keep = jax.tree.map(jnp.copy, self.state.params)
            flat, tree = jax.tree.flatten(self.state.params)
            flat[0] = jnp.full_like(flat[0], jnp.nan)
            self.state = self.state.replace(
                params=jax.tree.unflatten(tree, flat)
            )
        try:
            self.state, metrics = self._step_fn(self.state, batch)
        except Exception as e:
            # remat_skip defaults (configs.py LM_1B3/HYBRID_1B3) are tuned
            # to exactly fit ONE 16GB v5e at the benched batch x T; any
            # other topology/batch/accelerator inheriting them may fail to
            # compile where skip=0 fits. Retry once fully rematted instead
            # of dying (ADVICE r3 #1). Math is identical — only the
            # recompute/memory trade changes.
            msg = str(e)
            oom = "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
            if not (oom and self.cfg.model.remat_skip and self.model.cfg.remat_skip):
                raise
            # only compile-time OOM is recoverable: an execution-time OOM
            # fires after donation already invalidated the state buffers
            if any(
                getattr(x, "is_deleted", lambda: False)()
                for x in jax.tree.leaves(self.state)
            ):
                raise
            import warnings

            warnings.warn(
                f"train step OOM'd at remat_skip={self.model.cfg.remat_skip} "
                f"({msg.splitlines()[0][:120]}); retrying fully rematted "
                "(remat_skip=0)",
                stacklevel=2,
            )
            self.model = TransformerLM(
                dataclasses.replace(self.cfg.model, remat_skip=0),
                mesh=self.mesh,
            )
            self._step_fn = jax.jit(
                self._train_step,
                donate_argnums=(0,),
                in_shardings=(self.state_shardings, self.batch_shd),
                out_shardings=(self.state_shardings, None),
            )
            self._eval_fn = jax.jit(
                self._eval_step,
                in_shardings=(self.state_shardings.params, self.batch_shd),
            )
            self.state, metrics = self._step_fn(self.state, batch)
        if keep is not None:
            # the skipped update propagated the poisoned leaf as "old
            # value"; swap the clean pre-step params back in
            self.state = self.state.replace(params=keep)
        return metrics

    def train(
        self, data_iter, logger=None, ckpt=None, hook=None, eval_iter=None,
        eval_factory=None, preempt=None, watchdog=None,
    ) -> Dict[str, float]:
        """Run cfg.steps - state.step steps. Returns last metrics (host).
        ``eval_iter`` + cfg.eval_every > 0 interleaves held-out evals.
        ``eval_factory(step) -> iterator`` makes each eval's batches a pure
        function of the TRAIN step (resume-deterministic — a long-lived
        eval_iter's position depends on how many evals this process has
        already run, so a resumed run re-samples different batches).

        ``preempt`` (resilience/preempt.py PreemptionGuard): when its
        ``should_stop`` flips, stop at the step boundary, force an
        emergency checkpoint, and return with ``self.preempted_at`` set —
        the run resumes from exactly this step. ``watchdog``
        (resilience/watchdog.py) gets one heartbeat per step."""
        cfg = self.cfg
        tokens_per_step = cfg.batch_size * cfg.seq_len
        last: Dict[str, float] = {}
        start_step = int(self.state.step)
        # each phase of an iteration is ONE ``with self.trace.span``: the
        # step record, the tracer's ring and a running capture's host lines
        with contextlib.closing(self._steps(
            range(start_step + 1, cfg.steps + 1), tokens_per_step, logger,
        )) as steps:
            for step in steps:
                if watchdog is not None:
                    watchdog.beat(f"train step {step}")
                with self.trace.span("train.next_batch", "step", step=step), \
                        self._once("setup.loader"):
                    batch = next(data_iter)
                # host time to enqueue the step, no wait for the device; the
                # first call traces, lowers and compiles or loads it
                with self.trace.span("train.dispatch", "step", step=step), \
                        self._once("setup.first_step") as first:
                    if first is NULL_SPAN:
                        metrics = self.step(batch)
                    else:
                        metrics = self._first_step(batch, first)
                # only materialize metrics on the host at log cadence — reading a
                # device scalar every step would serialize the pipeline
                if step % cfg.log_every == 0 or step == cfg.steps:
                    # cumulative device-side counter: catches non-finite steps
                    # that happened *between* log points too; reading it
                    # waits for the step
                    with self.trace.span("train.log_readback", "step", step=step):
                        nf_total = int(metrics["nonfinite_total"])
                    if nf_total > self.nonfinite_steps:
                        # black-box the non-finite step window (the flight
                        # recorder is the training run's post-mortem ring,
                        # same spine as serving's — obs/flight.py)
                        _flight.record("train_nonfinite", step=step,
                                       total=nf_total)
                        self.nonfinite_steps = nf_total
                        if cfg.nan_policy == "halt":
                            _flight.recorder().dump("train-nan-halt")
                            # emergency checkpoint BEFORE halting: the offending
                            # state must be post-mortem restorable (params are
                            # the pre-skip values, counter included)
                            if watchdog is not None:
                                watchdog.disarm()  # don't escalate vs the save
                            if ckpt is not None:
                                self._save(ckpt, step, force=True)
                            raise FloatingPointError(
                                f"{nf_total} non-finite step(s) by step {step}"
                                + (
                                    f"; emergency checkpoint saved at step {step}"
                                    if ckpt is not None else ""
                                )
                            )
                    last = {k: float(v) for k, v in metrics.items()}
                    last["ppl"] = float(jnp.exp(jnp.minimum(last["loss"], 20.0)))
                    if logger:
                        self._show_compiles(logger.registry)
                        logger.log(step, last, tokens_per_step)
                    if self.trace.path:  # the CLI's --trace-path
                        self.trace.flush()
                if (
                    (eval_iter is not None or eval_factory is not None)
                    and cfg.eval_every
                    and (step % cfg.eval_every == 0 or step == cfg.steps)
                ):
                    if watchdog is not None:
                        # an eval pass (first one includes its jit compile) may
                        # legitimately exceed one step's budget — suspend stall
                        # detection across it rather than misread it as a hang;
                        # a hung EVAL DATA read is still caught by the eval
                        # loader's own stall_timeout (train.py)
                        watchdog.disarm()
                    with self.trace.span("train.eval", "step", step=step):
                        ev = self.evaluate(
                            eval_factory(step) if eval_factory is not None
                            else eval_iter
                        )
                    last.update(ev)
                    if logger:
                        logger.log(step, ev)
                    if watchdog is not None:
                        watchdog.arm(f"train step {step} (post-eval)")
                if ckpt is not None:
                    self._save(ckpt, step)
                if hook is not None:
                    # the caller's time (the benchmark's hook waits here
                    # for the step before)
                    with self.trace.span("train.hook", "step", step=step):
                        hook(step, metrics)
                if self._first_loss is not None and self._first_loss.is_ready():
                    # the loop's own log or hook has waited for the first
                    # step (asked, not waited for, here): set-up is over
                    self._first_loss = None
                    self.trace.instant("setup.ready", "setup", step=step)
                # chaos harness: simulated preemption delivers a real signal
                # here; the installed guard's handler runs synchronously and
                # flips should_stop before the check below
                _inject.fire("train.step_boundary", step=step)
                if preempt is not None and preempt.should_stop:
                    # graceful stop at the step boundary (the only place the
                    # state is consistent): emergency checkpoint, then return
                    # resumable — maybe_save is idempotent per step, so a
                    # cadence save this same step isn't re-written
                    if watchdog is not None:
                        # the save may take longer than one step budget; the
                        # watchdog must not escalate against the very save its
                        # stall action triggered
                        watchdog.disarm()
                    if ckpt is not None:
                        self._save(ckpt, step, force=True)
                    self.preempted_at = step
                    _flight.record("train_preempt", step=step,
                                   signum=getattr(preempt, "signum", None))
                    _flight.recorder().dump("train-preempt")
                    if not last:  # waits for this last step
                        with self.trace.span("train.log_readback", "step", step=step):
                            last = {k: float(v) for k, v in metrics.items()}
                    break
        if not last and start_step < cfg.steps:
            last = {k: float(v) for k, v in metrics.items()}
        return last

    def _steps(self, steps, tokens: int, logger):
        """``for step in self._steps(...)``: the loop's ``train.step`` spans
        (obs/trace.py, category ``step``). Each runs from the top of one
        iteration to the top of the next, or to the loop's exit, and starts
        where the one before ended, so that they tile the loop; its period
        goes to the logger's ``step_time_ms`` histogram. For the length of
        the loop the tracer holds the profiler's annotation factory (every
        span is then also a host line of whatever capture runs) and hears
        the interpreter's collections (``host.gc``); ``close()`` ends the
        open span and leaves the tracer and ``gc.callbacks`` as found."""
        trace = self.trace
        found, trace.annotate = trace.annotate, annotate
        end = None
        try:
            with trace.gc_events():
                for step in steps:
                    span = trace.span("train.step", "step", step=step, tokens=tokens)
                    try:
                        with span:
                            if end is not None:
                                span.start = end
                            yield step
                    finally:
                        end = span.start + span.dur
                        if logger is not None:
                            logger.observe_step(1e3 * span.dur)
        finally:
            trace.annotate = found

    def _save(self, ckpt, step: int, force: bool = False) -> None:
        """A cadence save, or a forced one waited for, as ``train.checkpoint``."""
        with self.trace.span("train.checkpoint", "step", step=step):
            ckpt.maybe_save(step, self.state, force=force)
            if force:
                ckpt.wait()

    def evaluate(self, data_iter, n_batches: Optional[int] = None) -> Dict[str, float]:
        assert self.state is not None, (
            "Trainer was built with materialize=False (AOT planning only)"
        )
        n = n_batches or self.cfg.eval_batches
        total, count = 0.0, 0.0
        for _ in range(n):
            batch = next(data_iter)
            with self._once("setup.first_eval"):
                s, c = self._eval_fn(self.state.params, batch)
            total += float(s)
            count += float(c)
        loss = total / max(count, 1.0)
        return {"eval_loss": loss, "eval_ppl": float(jnp.exp(jnp.minimum(loss, 20.0)))}

    # -- checkpoint glue ----------------------------------------------------

    def abstract_state(self):
        def leaf(s, shd):
            return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shd)

        return jax.tree.map(leaf, self._abstract, self.state_shardings)

    def restore(self, ckpt, step: Optional[int] = None):
        with self.trace.span("setup.restore", "setup"):
            self.state = ckpt.restore(self.abstract_state(), step)
        # sync the host-side counter so halt-mode doesn't re-raise for bad
        # steps that happened (and were handled) before the checkpoint
        self.nonfinite_steps = int(self.state.nonfinite)
        return int(self.state.step)


__all__ = [
    "Trainer", "TrainConfig", "TrainState", "lm_loss", "make_optimizer",
    "sr_round_bf16", "storage_cast",
]
