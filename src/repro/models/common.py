"""Shared model plumbing: config dataclass, norms, activations, init —
and the ONE dense-apply dispatch point of quantized-resident serving
(:func:`dense` / :func:`expert_dense` / :func:`embed_lookup`)."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.quantize import QuantizedTensor
from repro.kernels import ops

# Trace-time serving-mesh stack (see :func:`serving_mesh`): non-empty
# top means the dispatch helpers below pin their outputs replicated.
_SERVING_MESH: list = [None]


@contextlib.contextmanager
def serving_mesh(mesh):
    """While active (at *trace* time), every dispatch-helper output is
    pinned replicated on ``mesh`` via ``with_sharding_constraint``.

    This is the whole trick that makes sharded serving token-identical
    to single-device: GSPMD only reorders float reductions when a
    *contraction* dim is sharded, and with every activation pinned
    replicated, each matmul sees a replicated input against a weight
    sharded on a non-contraction dim (see ``serving_spec_for_param``) —
    the only collectives are output all-gathers, pure data movement,
    bit-exact. Quantized weights take the same route through
    ``ops.sharded_dequant_matmul`` (one kernel launch per shard). The
    engines wrap their jitted model entry points in this context
    (``PrecisionManagedEngine._meshed``); with no mesh active the
    helpers are byte-for-byte the single-device code path."""
    _SERVING_MESH.append(mesh)
    try:
        yield
    finally:
        _SERVING_MESH.pop()


def launch(kernel, *args, **static):
    """Call a kernel entry point of :mod:`repro.kernels.ops`. Under an
    active serving mesh the call becomes one launch per device on
    replicated operands: GSPMD cannot partition a Mosaic kernel, so
    every kernel in a sharded program sits inside a ``shard_map``.
    ``static`` holds the entry point's keyword options."""
    mesh = _SERVING_MESH[-1]
    fn = functools.partial(kernel, **static)
    if mesh is None:
        return fn(*args)
    P = jax.sharding.PartitionSpec
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)(*args)


def _pin_replicated(y: jax.Array) -> jax.Array:
    mesh = _SERVING_MESH[-1]
    if mesh is None:
        return y
    return jax.lax.with_sharding_constraint(
        y, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()))


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    """One architecture. ``cycle`` is the repeating block pattern; layers
    = len(cycle) * n_cycles + len(tail). Block kinds:

    attn        full-attention decoder block (GQA + MLP, gated or not)
    swa         sliding-window attention block (window=cfg.window)
    global      full attention (gemma3 naming, distinct rope_theta)
    moe         attention + MoE FFN (full attn)
    swa_moe     sliding-window attention + MoE FFN (mixtral)
    cross       cross-attention block (VLM image layers)
    selfcross   self-attn + cross-attn + MLP in one block (enc-dec decoder)
    mamba2      Mamba-2 SSD block
    slstm       xLSTM sLSTM block
    mlstm       xLSTM mLSTM block
    shared_attn Zamba2 shared transformer block (one weight set reused)
    enc_attn    bidirectional encoder block
    """

    name: str
    family: str  # dense | moe | ssm | hybrid | audio | vlm | cnn
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    cycle: tuple[str, ...] = ("attn",)
    head_dim: int | None = None
    # attention
    rope_theta: float = 10_000.0
    # rotate only the first rotary_dim dims of each head (pairs i and
    # i + rotary_dim/2, as HF Nemotron's partial rotary); None = all
    rotary_dim: int | None = None
    window: int = 0  # sliding window width for swa/local blocks
    qk_norm: bool = False
    norm_type: str = "rmsnorm"  # rmsnorm | layernorm | layernorm1p | nonparam_ln
    act: str = "silu"  # silu | gelu | relu2
    # MLP: wo(act(wi_gate x) * wi_up x) when gated, else wo(act(wi_up x))
    mlp_gated: bool = True
    attn_chunk: int = 1024  # online-softmax KV chunk
    # moe
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # ssm
    ssm_state: int = 0
    ssm_heads: int = 0
    ssm_expand: int = 2
    conv_width: int = 4
    ssm_chunk: int = 256
    # xlstm
    lstm_proj_factor: float = 2.0
    # enc-dec (audio)
    enc_layers: int = 0
    enc_seq_divisor: int = 4  # encoder frames = seq // divisor
    # vlm
    vision_tokens: int = 0
    d_vision: int = 0
    # input / output
    embed_scale: bool = True  # multiply token embeddings by sqrt(d_model)
    tie_embeddings: bool = True
    logit_softcap: float = 0.0
    # compute dtype for activations
    dtype: Any = jnp.bfloat16
    # storage dtype for parameters. fp32 = training default (master
    # weights); bf16 halves the resident weight bytes + HBM traffic for
    # serving (§Perf iteration; quantized-resident serving
    # (ProgressiveServer(resident="quantized")) goes further, to k/16
    # of bf16, with no fp copy at all)
    param_dtype: Any = jnp.float32
    # rematerialize cycle bodies in the training forward (memory/compute
    # trade; §Perf iterates on this)
    remat: bool = True
    # costing mode: unroll every lax.scan (cycle stack, attention chunks,
    # SSD chunks, CE chunks) so compiled.cost_analysis() counts loop
    # bodies x trip_count. XLA's HLO cost analysis visits a while-loop
    # body ONCE (verified; see EXPERIMENTS.md §Dry-run), so the scanned
    # production model undercounts FLOPs/bytes/collectives by the trip
    # counts. The costing variant is mathematically identical (scan
    # unrolling does not change the computed function); only HLO size
    # and compile time differ. Never use for real training.
    costing: bool = False

    def for_costing(self) -> "ArchConfig":
        import dataclasses as _dc

        return _dc.replace(self, costing=True)

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def n_cycles(self) -> int:
        return self.n_layers // len(self.cycle)

    @property
    def tail(self) -> tuple[str, ...]:
        """Remainder blocks after full cycles, continuing the pattern."""
        r = self.n_layers % len(self.cycle)
        return self.cycle[:r]

    @property
    def uses_cross(self) -> bool:
        return any(k in ("cross", "selfcross") for k in self.cycle)

    @property
    def is_subquadratic(self) -> bool:
        """True when no block does *unwindowed* attention over the full
        sequence during prefill (SSM/SWA mixes count; a minority of
        'global' layers is allowed for decode-only long-context shapes)."""
        quad = {"attn", "moe", "cross", "selfcross", "enc_attn"}
        return not any(k in quad for k in self.cycle)

    def reduced(self, **overrides) -> "ArchConfig":
        """Smoke-test variant: same family/pattern, tiny dims."""
        small = dict(
            n_layers=max(2, len(self.cycle)),
            d_model=min(self.d_model, 128),
            n_heads=min(self.n_heads, 4),
            n_kv=min(self.n_kv, 2),
            d_ff=min(self.d_ff, 256) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            head_dim=32 if self.head_dim else None,
            n_experts=min(self.n_experts, 4) if self.n_experts else 0,
            top_k=min(self.top_k, 2) if self.top_k else 0,
            # drop-free capacity (cf >= E/K) so prefill==decode exactly in
            # consistency tests; production configs keep the real cf.
            capacity_factor=4.0 if self.n_experts else self.capacity_factor,
            ssm_state=min(self.ssm_state, 16) if self.ssm_state else 0,
            ssm_heads=min(self.ssm_heads, 2) if self.ssm_heads else 0,
            enc_layers=min(self.enc_layers, 2) if self.enc_layers else 0,
            vision_tokens=min(self.vision_tokens, 16) if self.vision_tokens else 0,
            d_vision=min(self.d_vision, 64) if self.d_vision else 0,
            window=min(self.window, 16) if self.window else 0,
            attn_chunk=16,
            ssm_chunk=8,
            dtype=jnp.float32,
        )
        # keep n_kv dividing n_heads
        if small["n_heads"] % max(small["n_kv"], 1):
            small["n_kv"] = 1
        small.update(overrides)
        if self.rotary_dim and "rotary_dim" not in overrides:
            # keep the rotated share of each head
            hd = small["head_dim"] or small["d_model"] // small["n_heads"]
            small["rotary_dim"] = hd * self.rotary_dim // self.hd
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# Norms & activations
# ---------------------------------------------------------------------------

def norm_init(cfg: ArchConfig, d: int):
    if cfg.norm_type == "rmsnorm":
        return {"scale": jnp.ones((d,), jnp.float32)}
    if cfg.norm_type == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    if cfg.norm_type == "layernorm1p":  # Nemotron: applied as 1 + scale
        return {"scale": jnp.zeros((d,), jnp.float32), "bias": jnp.zeros((d,), jnp.float32)}
    if cfg.norm_type == "nonparam_ln":  # OLMo: LN without affine params
        return {}
    raise ValueError(cfg.norm_type)


def apply_norm(cfg: ArchConfig, p, x: jax.Array) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm_type == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + 1e-6)
        y = y * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + 1e-5)
        if cfg.norm_type == "layernorm":
            y = y * p["scale"] + p["bias"]
        elif cfg.norm_type == "layernorm1p":
            y = y * (1.0 + p["scale"]) + p["bias"]
    return y.astype(x.dtype)


def activation(cfg: ArchConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        return jax.nn.silu(x)
    if cfg.act == "gelu":
        return jax.nn.gelu(x)
    if cfg.act == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise ValueError(cfg.act)


def dense_init(key, d_in: int, d_out: int) -> jax.Array:
    scale = (2.0 / (d_in + d_out)) ** 0.5
    return scale * jax.random.normal(key, (d_in, d_out), jnp.float32)


# ---------------------------------------------------------------------------
# Quantized-resident dispatch
#
# Every matmul in transformer.py / attention.py / moe.py / model.py goes
# through one of these three helpers. A parameter leaf is either a plain
# float array (materialized path: cast + matmul, exactly the old code)
# or a live QuantizedTensor riding the PlaneStore accumulator, in which
# case eq. (5) is fused into the MXU feed via ops.dequant_matmul — the
# fp weight never exists outside a VMEM tile. Call sites never branch;
# this is the single dispatch point.
# ---------------------------------------------------------------------------

# Leaf basenames that are consumed exclusively through the dispatch
# helpers below and may therefore stay quantized in HBM. (Norm/gate
# vectors, conv kernels and recurrence matrices keep the materialized
# path — they're tiny and not matmul-shaped.)
QUANTIZED_RESIDENT_LEAVES = frozenset({
    "wq", "wk", "wv", "wo", "wi_gate", "wi_up",          # attention + GLU MLP
    "router", "we_gate", "we_up", "we_down",             # MoE
    "embed", "lm_head", "vision_proj",                   # I/O surfaces
    "in_proj", "out_proj", "up_proj", "down_proj",       # SSM/xLSTM projections
    "w_in", "w_if",
})


def leaf_basename(key) -> str:
    """Last component of a PlaneStore leaf key — a jax tree path tuple
    (pull-mode stores) or a 'a/b/c' path string (wire-fed stores)."""
    if isinstance(key, str):
        return key.rsplit("/", 1)[-1]
    last = key[-1]
    for attr in ("key", "idx", "name"):
        if hasattr(last, attr):
            return str(getattr(last, attr))
    return str(last)


def quantized_resident_eligible(key) -> bool:
    """The default ``eligible`` predicate for
    :meth:`~repro.core.plane_store.PlaneStore.quantized_leaves`."""
    return leaf_basename(key) in QUANTIZED_RESIDENT_LEAVES


def masked_q(w: QuantizedTensor, q: jax.Array | None = None,
             keep: jax.Array | None = None) -> jax.Array:
    """Apply a truncated view's deferred plane mask: keep only the top
    ``keep_bits`` bits of the accumulator, on the fly. The full-view
    ``keep_bits is None`` case is a structural no-op (no masking ops in
    the jaxpr), so the plain quantized-resident path is untouched. The
    mask runs inside the consuming jit — the masked uint is a transient
    fusion input, never a resident buffer."""
    q = w.q if q is None else q
    keep = w.keep_bits if keep is None else keep
    if keep is None:
        return q
    shift = (jnp.int32(w.bits) - keep.astype(jnp.int32)).astype(q.dtype)
    return (q >> shift) << shift


def _dequant_matmul(x: jax.Array, q: jax.Array, scale, offset) -> jax.Array:
    """The fused kernel on a 2-D ``x``. Under a serving mesh whose model
    axis divides N it runs once per shard on that shard's own output
    columns (no weight crosses a chip); otherwise once per device."""
    mesh = _SERVING_MESH[-1]
    if mesh is not None and q.shape[-1] % mesh.shape["model"] == 0:
        return ops.sharded_dequant_matmul(x, q, scale, offset, mesh=mesh)
    return launch(ops.dequant_matmul, x, q, scale, offset)


def dense(x: jax.Array, w, *, dtype) -> jax.Array:
    """``x @ w`` with ``w`` either a float array (cast to ``dtype``,
    plain matmul) or a QuantizedTensor (fused dequant-matmul; f32
    accumulation, output cast to ``dtype``). x: (..., K); w: (K, N)."""
    if isinstance(w, QuantizedTensor):
        lead = x.shape[:-1]
        y = _dequant_matmul(x.reshape(-1, x.shape[-1]), masked_q(w),
                            w.scale, w.offset)
        return _pin_replicated(y.reshape(*lead, w.q.shape[-1])).astype(dtype)
    return _pin_replicated(x @ w.astype(dtype))


def expert_dense(x: jax.Array, w, *, dtype) -> jax.Array:
    """Per-expert matmul ``einsum('becd,edf->becf')``. Quantized path:
    one fused dequant-matmul per expert (E is static and small), each
    fed its own (1, 1) affine slice — expert banks sliced per expert by
    the division policy keep their per-slice quantization ranges."""
    if isinstance(w, QuantizedTensor):
        B, E, C, d = x.shape
        outs = []
        for e in range(E):
            qe = masked_q(w, w.q[e],
                          None if w.keep_bits is None else w.keep_bits[e])
            ye = _dequant_matmul(x[:, e].reshape(B * C, d), qe,
                                 w.scale[e], w.offset[e])
            outs.append(ye.reshape(B, C, -1))
        return _pin_replicated(jnp.stack(outs, axis=1)).astype(dtype)
    return _pin_replicated(jnp.einsum("becd,edf->becf", x, w.astype(dtype)))


def embed_lookup(w, tokens: jax.Array) -> jax.Array:
    """Embedding-row gather. Quantized path gathers the *uint* rows and
    applies the eq.-(5) affine to just those rows — the fp table never
    materializes. Returns float32 rows (callers cast)."""
    if isinstance(w, QuantizedTensor):
        rows = masked_q(w, w.q[tokens]).astype(jnp.float32)
        return _pin_replicated(rows * w.scale.reshape(())
                               + w.offset.reshape(()))
    return _pin_replicated(w[tokens].astype(jnp.float32))


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap else x
