"""ProgressiveModel: the paper's pipeline (Fig. 3) lifted to pytrees.

Server side (once, before deployment):
    ``divide(params, policy)`` -> ProgressiveModel
        quantize every float leaf (eq. 2), bit-divide it (eq. 3), and
        organize planes into transmission *stages*.

Client side (per stage arrival):
    ``ReceiverState.receive(stage)`` OR-accumulates planes (eq. 4);
    ``ReceiverState.materialize()`` dequantizes (eq. 5) into a params
    pytree of the original structure/dtypes, usable by the unmodified
    model ``apply``.

Non-float leaves (ints, bools — e.g. RoPE tables built on the fly don't
exist in params, but masks might) ship verbatim in stage 1.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitplanes
from repro.core.plane_store import PlaneStore
from repro.core.policy import DivisionPolicy, UniformPolicy, TensorPlan
from repro.core.quantize import quantize


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


@dataclasses.dataclass
class TensorPlanes:
    """Server-side per-tensor artifact: metadata + all planes.

    A leaf may be sliced along ``slice_axis`` (expert banks): one
    TensorPlanes per slice, each with its own (lo, hi) range and
    priority; ``shape`` is then the slice's shape (axis removed) and the
    receiver stacks slices back along ``slice_axis``."""

    path: tuple
    plan: TensorPlan
    lo: jax.Array
    hi: jax.Array
    shape: tuple
    orig_dtype: Any
    planes: list[np.ndarray]  # MSB-first, len == n_planes, host memory
    slice_axis: int | None = None
    slice_idx: int = 0
    n_slices: int = 1

    @property
    def bits(self) -> int:
        return self.plan.schedule.bits


@dataclasses.dataclass
class ProgressiveModel:
    """The divided model, ready for staged transmission."""

    tensors: list[TensorPlanes]
    treedef: Any
    n_stages: int
    passthrough: list[tuple[tuple, Any]]  # (path, non-float leaf)

    def stage(self, s: int) -> list[tuple[int, jax.Array]]:
        """Planes shipped in stage s (1-indexed): [(tensor_idx, plane)],
        ordered by the policy's priority."""
        if not (1 <= s <= self.n_stages):
            raise ValueError(f"stage {s} outside [1, {self.n_stages}]")
        out = []
        for i, t in enumerate(self.tensors):
            if s <= t.plan.schedule.n_planes:
                out.append((i, t.planes[s - 1]))
        out.sort(key=lambda it: (self.tensors[it[0]].plan.priority, it[0]))
        return out

    def stage_payload_bytes(self, s: int) -> int:
        total = 0
        for i, plane in self.stage(s):
            t = self.tensors[i]
            w = t.plan.schedule.widths[s - 1]
            total += -(-int(np.prod(t.shape)) * w // 8)  # ceil
        return total

    def total_payload_bytes(self) -> int:
        return sum(self.stage_payload_bytes(s) for s in range(1, self.n_stages + 1))

    def singleton_payload_bytes(self) -> int:
        """Bytes of the non-progressive k-bit quantized model (the
        paper's baseline). total_payload_bytes() equals this up to
        per-plane byte-boundary padding (< 1 byte per plane per tensor)
        — the paper's 'no size increase' property. See
        ``padding_overhead_bound``."""
        total = 0
        for t in self.tensors:
            total += -(-int(np.prod(t.shape)) * t.bits // 8)
        return total

    def padding_overhead_bound(self) -> int:
        """Max extra wire bytes vs. singleton from rounding each plane up
        to a byte boundary."""
        return sum(t.plan.schedule.n_planes for t in self.tensors)


def divide(params, policy: DivisionPolicy | None = None) -> ProgressiveModel:
    """Quantize + bit-divide a params pytree (paper steps 1-2)."""
    policy = policy or UniformPolicy()
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    tensors: list[TensorPlanes] = []
    passthrough: list[tuple[tuple, Any]] = []
    for path, leaf in leaves_with_paths:
        if not _is_float(leaf):
            passthrough.append((path, leaf))
            continue
        arr = jnp.asarray(leaf)
        axis = policy.slice_spec(path, arr.shape)
        if axis is None:
            slices = [(None, 0, 1, arr)]
        else:
            n = arr.shape[axis]
            slices = [(axis, e, n, jnp.take(arr, e, axis=axis))
                      for e in range(n)]
        for slice_axis, idx, n_slices, sub in slices:
            plan = policy.plan(path, sub.shape, arr.dtype,
                               slice_idx=None if slice_axis is None else idx)
            # Eager ops allocate their outputs when dispatched, so the
            # temporaries of everything not yet run are live at once:
            # wait for the codes before splitting. Planes are the wire's
            # source, not the device's: each moves to host memory as soon
            # as it is split (np.asarray waits for it), so the accelerator
            # never holds a whole model's planes next to its params.
            qt = quantize(sub, plan.schedule.bits)
            qt.q.block_until_ready()
            widths = plan.schedule.widths
            planes = [np.asarray(bitplanes.split_plane(qt.q, qt.bits,
                                                       widths, m))
                      for m in range(1, len(widths) + 1)]
            tensors.append(
                TensorPlanes(
                    path=path,
                    plan=plan,
                    lo=qt.lo,
                    hi=qt.hi,
                    shape=tuple(sub.shape),
                    orig_dtype=arr.dtype,
                    planes=planes,
                    slice_axis=slice_axis,
                    slice_idx=idx,
                    n_slices=n_slices,
                )
            )
    return ProgressiveModel(
        tensors=tensors,
        treedef=treedef,
        n_stages=policy.n_stages,
        passthrough=passthrough,
    )


@dataclasses.dataclass
class ReceiverState:
    """Client-side accumulator (paper steps 3-4), a thin functional
    shell over the shared :class:`~repro.core.plane_store.PlaneStore`.

    ``receive`` is the eq. (4) OR — one batched integer Pallas launch
    per container dtype, no float work — and ``materialize`` is eq. (5),
    incremental: tensors that received nothing since the last call come
    back from the store's leaf cache. The store is device-resident, so
    in the serving engine a precision upgrade never stalls decoding.
    """

    model_meta: ProgressiveModel  # planes unused client-side; meta only
    store: PlaneStore
    received_stages: int = 0

    @classmethod
    def init(cls, model: ProgressiveModel, *, mesh=None) -> "ReceiverState":
        """``mesh=None`` (default): single-device flat-buffer store.
        With a serving mesh, the accumulators shard across its model
        axis (:class:`~repro.core.plane_store.ShardedPlaneStore`) along
        the same axes ``launch.sharding.serving_spec_for_param`` gives
        the params they back — same eq. (4)/(5) semantics, shard-local
        ingest."""
        if mesh is not None:
            from repro.core.plane_store import ShardedPlaneStore
            return cls(model_meta=model,
                       store=ShardedPlaneStore.from_model(model, mesh),
                       received_stages=0)
        return cls(model_meta=model, store=PlaneStore.from_model(model),
                   received_stages=0)

    @property
    def acc(self) -> list[jax.Array]:
        """Per-tensor accumulator views (compat with the pre-PlaneStore
        API; the storage is the store's flat buffers)."""
        return [self.store.acc(i) for i in range(self.store.n_tensors)]

    def receive(self, stage_planes: Sequence[tuple[int, jax.Array]]) -> "ReceiverState":
        store = self.store.copy()
        store.ingest(stage_planes)
        return dataclasses.replace(
            self, store=store, received_stages=self.received_stages + 1)

    def effective_bits(self, tensor_idx: int) -> int:
        return self.store.effective_bits(tensor_idx)

    def materialize(self):
        """Dequantize the current accumulators into the original pytree
        (stacking sliced tensors back along their slice axis)."""
        return rebuild_params(self.model_meta, self.store.materialize_leaves())

    def materialize_resident(self, eligible=None, *, bits=None):
        """The quantized-resident view of the same pytree: eligible
        weight leaves stay :class:`~repro.core.quantize.QuantizedTensor`
        views over the store's accumulators (no fp copy); the rest
        dequantize as in :meth:`materialize`. ``eligible`` defaults to
        the model dispatch's matmul-leaf predicate — a bare ``None``
        would quantize every >=2-D leaf, including ones (conv kernels,
        recurrence matrices) the model consumes without dispatch.
        ``bits=b`` hands out the truncated-precision draft view instead
        (same accumulators, deferred plane mask — zero extra weight
        bytes; see ``PlaneStore.quantized_leaves``)."""
        if eligible is None:
            from repro.models.common import quantized_resident_eligible
            eligible = quantized_resident_eligible
        return rebuild_params(
            self.model_meta,
            self.store.quantized_leaves(eligible=eligible, bits=bits))


def rebuild_params(model: ProgressiveModel, tensor_leaves: Mapping,
                   *, key_fn: Callable[[tuple], Any] | None = None):
    """Rebuild the original params pytree from materialized float leaves.

    ``tensor_leaves`` maps ``key_fn(path)`` -> dequantized array (one
    entry per *leaf*; sliced tensors are already restacked by the
    store). Non-float passthrough leaves come from the model meta. The
    default key is the raw path tuple (``ReceiverState``); the wire
    client keys its store by ``wire.path_str``, so a server sitting on a
    wire-fed store passes ``key_fn=wire.path_str``.
    """
    key_fn = key_fn or (lambda p: p)
    ordered = []
    for path, kind in _all_paths(model):
        ordered.append(kind[1] if kind[0] == "p"
                       else tensor_leaves[key_fn(path)])
    return jax.tree_util.tree_unflatten(model.treedef, ordered)


def _all_paths(model: ProgressiveModel):
    """All (path, kind) in original flatten order."""
    tensor_paths = {t.path: ("t", i) for i, t in enumerate(model.tensors)}
    pass_paths = {p: ("p", leaf) for p, leaf in model.passthrough}
    # tree_flatten_with_path order == tree_flatten order; reconstruct it
    # from the union, sorted by the order we saw them (tensors and
    # passthrough were appended in flatten order, so merge by key lookup).
    # We stored them separately; rebuild by walking both lists.
    merged: list[tuple[tuple, Any]] = []
    ti = pi = 0
    # flatten order is recoverable because each path appears exactly once;
    # we re-flatten a skeleton of the treedef to get the order.
    n = len({t.path for t in model.tensors}) + len(model.passthrough)
    skeleton = jax.tree_util.tree_unflatten(model.treedef, list(range(n)))
    flat, _ = jax.tree_util.tree_flatten_with_path(skeleton)
    for path, _leaf in flat:
        merged.append((path, tensor_paths.get(path) or pass_paths.get(path)))
    return merged


def transmit_reconstruct(params, policy: DivisionPolicy | None = None, upto_stage: int | None = None):
    """One-shot helper: divide, 'transmit' stages [1..upto], materialize.

    The workhorse of tests and accuracy benchmarks: returns the
    approximate params a client would hold after ``upto_stage`` stages.
    """
    model = divide(params, policy)
    upto = model.n_stages if upto_stage is None else upto_stage
    st = ReceiverState.init(model)
    for s in range(1, upto + 1):
        st = st.receive(model.stage(s))
    return st.materialize()
