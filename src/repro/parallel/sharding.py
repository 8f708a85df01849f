"""Logical-axis sharding rules: DP / FSDP(ZeRO) / TP / EP / SP on one mesh.

Model code annotates every parameter (and activation constraint point) with
*logical* axes ("embed", "heads", "vocab", "expert", "batch", ...).  This
module maps them to mesh axes with per-dimension divisibility checks — an
axis that does not divide evenly is left unsharded (replicated) and the drop
is recorded, which is what makes one rule set work across all 10 assigned
archs (e.g. qwen2's 14 heads on a 16-way model axis).

Key rules (see DESIGN.md §4):
  batch     -> ("pod", "data")   data parallelism (pod axis = DP by default)
  heads/mlp/vocab/expert -> "model"   tensor / expert parallelism
  embed     -> "data" when cfg.fsdp  (ZeRO-3: 2-D param sharding data x model)
  cache_seq -> "model"           context-parallel flash decoding
  seq       -> "model" when SP   sequence parallelism for norm/residual work
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from collections import Counter
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisVal = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass
class AxisRules:
    mesh: Mesh
    rules: Dict[str, AxisVal]
    dropped: list = dataclasses.field(default_factory=list)
    # per-logical-axis drop counters: how many times each logical axis lost a
    # mesh axis to a divisibility fallback (sharding-regression visibility)
    drops_by_axis: Dict[str, int] = dataclasses.field(default_factory=dict)
    _warned: set = dataclasses.field(default_factory=set, repr=False)

    def _axis_size(self, mesh_axes: AxisVal) -> int:
        if mesh_axes is None:
            return 1
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        return int(np.prod([self.mesh.shape[a] for a in mesh_axes]))

    def _record_drop(self, shape, ax, mesh_axis, dim, product) -> None:
        self.dropped.append((tuple(shape), ax, mesh_axis, dim))
        self.drops_by_axis[ax] = self.drops_by_axis.get(ax, 0) + 1
        key = (ax, mesh_axis, dim, product)
        if key not in self._warned:  # one line per unique fallback, not per call
            self._warned.add(key)
            warnings.warn(
                f"sharding: dim {dim} (logical axis {ax!r}) is not divisible by "
                f"mesh-axis product {product} — dropping mesh axis {mesh_axis!r} "
                f"(replicating)",
                stacklevel=3,
            )

    def spec(self, shape: Sequence[int], axes: Sequence[Optional[str]]) -> P:
        """PartitionSpec for `shape` annotated with logical `axes`.

        Drops (replicates) any dim whose size is not divisible by the mapped
        mesh-axis product, and never uses a mesh axis twice in one spec.
        Every drop is warned once and counted in `drops_by_axis`."""
        used: set = set()
        out = []
        for dim, ax in zip(shape, axes):
            mesh_axes = self.rules.get(ax) if ax is not None else None
            if mesh_axes is None:
                out.append(None)
                continue
            tpl = (mesh_axes,) if isinstance(mesh_axes, str) else tuple(mesh_axes)
            tpl = tuple(a for a in tpl if a not in used and a in self.mesh.shape)
            # progressive fallback: drop trailing axes until the product divides
            while tpl and dim % int(np.prod([self.mesh.shape[a] for a in tpl])) != 0:
                prod = int(np.prod([self.mesh.shape[a] for a in tpl]))
                self._record_drop(shape, ax, tpl[-1], dim, prod)
                tpl = tpl[:-1]
            if not tpl:
                out.append(None)
                continue
            used.update(tpl)
            out.append(tpl[0] if len(tpl) == 1 else tpl)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding(self, shape, axes) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(shape, axes))


_state = threading.local()


def current_rules() -> Optional[AxisRules]:
    return getattr(_state, "rules", None)


@contextlib.contextmanager
def use_rules(rules: Optional[AxisRules]):
    prev = getattr(_state, "rules", None)
    _state.rules = rules
    try:
        yield rules
    finally:
        _state.rules = prev


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axes; no-op outside a rules ctx."""
    r = current_rules()
    if r is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, r.spec(x.shape, axes)))


def make_rules(mesh: Mesh, *, profile: str = "tp", fsdp: bool = False,
               seq_parallel: bool = False,
               expert_data_shard: bool = False) -> AxisRules:
    """Parallelism profiles:
      "tp"  — megatron-style TP on "model" + DP on ("pod","data") [+FSDP]
      "dp"  — small-model profile: pure DP, only the vocab/cache_seq dims use
              the model axis (qwen2-0.5b / xlstm-125m class)
    """
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if profile == "dp":
        rules: Dict[str, AxisVal] = {
            # small models spread the batch over every axis (1 seq/device at
            # 256 chips); progressive fallback drops "model" when it doesn't
            # divide (e.g. global_batch 256 on the 512-chip multi-pod mesh)
            "batch": data_axes + ("model",),
            "heads": None,
            "kv_heads": None,
            "mlp": None,
            "vocab": "model",
            "expert": "model",
            "embed": None,
            "cache_seq": "model",
            "seq": None,
            "expert_cap": None,
            "layers": None,
            "head_dim": None,
        }
        return AxisRules(mesh, rules)
    rules = {
        "batch": data_axes if data_axes else None,
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "vocab": "model",
        "expert": ("model", "data") if expert_data_shard else "model",
        "embed": (data_axes if fsdp else None),
        "cache_seq": "model",  # context-parallel decode
        "seq": ("model" if seq_parallel else None),
        "expert_cap": None,
        "layers": None,
        "head_dim": None,
    }
    return AxisRules(mesh, rules)


# ---------------------------------------------------------------------------
# Communication-overlapped collectives: the model-axis ring
# ---------------------------------------------------------------------------


def ring_topology(mesh: Mesh, axis: str = "model") -> Dict[str, Any]:
    """The bidirectional ring over one mesh axis: the jax analogue of the
    paper's 64-core cluster interconnect.  Returns the ppermute pairs for
    both directions (built by the same `ring_perm` the collective matmul
    kernels use) plus the ring size, for callers that need the topology
    explicitly (tests, benchmarks, debugging)."""
    from ..kernels.mx_collective_matmul import ring_perm

    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r}; axes: {tuple(mesh.shape)}")
    P_ = int(mesh.shape[axis])
    return {
        "axis": axis,
        "size": P_,
        "fwd": ring_perm(P_),
        "bwd": ring_perm(P_, reverse=True),
    }


@dataclasses.dataclass(frozen=True)
class CollectivePolicy:
    """Deployment decision for communication-overlapped TP projections.

    When active (see `collective_policy()`), `core.ops.linear(...,
    tp_mode=...)` routes eligible projections through the ring
    all-gather⊗matmul / matmul⊗reduce-scatter paths over `axis`, instead
    of letting GSPMD insert serialized collectives around the GEMM.

    ``routed`` counts, per traced projection, which path it took:
    (tp_mode, "ring"), or (tp_mode, "fallback") for one the ring could not
    take (N or K not divisible over the ring, a gated reduce-scatter, a
    sparse payload): GSPMD partitions it on the XLA backend, and a Pallas
    kernel runs on replicated operands (`core.ops.per_device`)."""

    mesh: Mesh
    axis: str = "model"
    direction: str = "bidir"  # "fwd" | "bwd" | "bidir"
    enabled: bool = True
    routed: Counter = dataclasses.field(default_factory=Counter,
                                        compare=False, repr=False)

    def __post_init__(self):
        if self.axis not in self.mesh.shape:
            raise ValueError(
                f"collective policy axis {self.axis!r} is not a mesh axis; "
                f"mesh has {tuple(self.mesh.shape)}"
            )
        if self.direction not in ("fwd", "bwd", "bidir"):
            raise ValueError(
                f"unknown ring direction {self.direction!r}; "
                "one of ('fwd', 'bwd', 'bidir')"
            )

    @property
    def axis_size(self) -> int:
        return int(self.mesh.shape[self.axis])

    def topology(self) -> Dict[str, Any]:
        return ring_topology(self.mesh, self.axis)


def autotune_collective_policy(
    mesh: Mesh,
    problems,
    *,
    axis: str = "model",
    ici_bw: float,
    peak_flops: float,
) -> tuple:
    """Pick the ring direction/chunk split from the `RingCollectiveGemm`
    transfer model instead of the fixed "bidir" default.

    ``problems`` is a sequence of (mode, GemmProblem) pairs — the layer's
    TP projections (qkv/attn_out/mlp_up/mlp_down/lm_head as built by
    dryrun.collective_gemm_reports).  Candidates are the two chunk
    schedules the ring kernels implement: "bidir" (each chunk split in
    half across both ring directions — per-link bytes halve) and "fwd"
    (whole chunks one way).  The model's overlapped time — first chunk
    GEMM, then P-1 rounds of max(compute, comm) — is summed over the
    problem set and the cheaper schedule wins; ties break toward "fwd"
    (fewer in-flight buffers).

    Returns (CollectivePolicy, report) where the report records the
    per-candidate times so dryrun can log the chosen schedule in its
    `collective_gemms` record."""
    from ..core.transfer_model import RingCollectiveGemm

    if axis not in mesh.shape:
        raise ValueError(f"mesh has no axis {axis!r}; axes: {tuple(mesh.shape)}")
    P_ = int(mesh.shape[axis])
    problems = list(problems)
    candidates = {"fwd": False, "bidir": True}
    totals: Dict[str, float] = {}
    exposed: Dict[str, float] = {}
    for name, bidir in candidates.items():
        t = e = 0.0
        for mode, prob in problems:
            ring = RingCollectiveGemm(mode=mode, axis_size=P_,
                                      bidirectional=bidir)
            t += ring.overlapped_time_s(prob, ici_bw=ici_bw,
                                        peak_flops=peak_flops)
            e += ring.exposed_comm_s(prob, ici_bw=ici_bw,
                                     peak_flops=peak_flops)
        totals[name] = t
        exposed[name] = e
    # strict improvement required: "fwd" wins ties
    chosen = "bidir" if totals["bidir"] < totals["fwd"] else "fwd"
    serialized = sum(
        RingCollectiveGemm(mode=mode, axis_size=P_,
                           bidirectional=candidates[chosen])
        .serialized_time_s(prob, ici_bw=ici_bw, peak_flops=peak_flops)
        for mode, prob in problems
    )
    report = {
        "axis": axis,
        "axis_size": P_,
        "chosen_direction": chosen,
        "candidate_time_s": totals,
        "candidate_exposed_comm_s": exposed,
        "serialized_time_s": serialized,
        "autotuned": True,
        "n_problems": len(problems),
    }
    policy = CollectivePolicy(mesh=mesh, axis=axis, direction=chosen,
                              enabled=P_ > 1)
    return policy, report


def current_collectives() -> Optional[CollectivePolicy]:
    pol = getattr(_state, "collectives", None)
    return pol if (pol is not None and pol.enabled) else None


@contextlib.contextmanager
def collective_policy(mesh: Optional[Mesh] = None, *, axis: str = "model",
                      direction: str = "bidir", enabled: bool = True,
                      policy: Optional[CollectivePolicy] = None):
    """Context under which eligible TP projections run as overlapped ring
    collective matmuls.  Pass a mesh (plus axis/direction) or a prebuilt
    CollectivePolicy; `enabled=False` (or exiting) restores the serialized
    GSPMD behavior."""
    if policy is None:
        if mesh is None:
            raise ValueError("collective_policy needs a mesh or a policy")
        policy = CollectivePolicy(mesh=mesh, axis=axis, direction=direction,
                                  enabled=enabled)
    prev = getattr(_state, "collectives", None)
    _state.collectives = policy
    try:
        yield policy
    finally:
        _state.collectives = prev


# ---------------------------------------------------------------------------
# Tree-level helpers
# ---------------------------------------------------------------------------


def _is_axes_leaf(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x)


def tree_specs(rules: AxisRules, abstract_tree, axes_tree):
    """PartitionSpec tree from abstract shapes + logical-axes trees."""
    return jax.tree.map(
        lambda s, ax: rules.spec(s.shape, ax),
        abstract_tree,
        axes_tree,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct) or _is_axes_leaf(x),
    )


def tree_shardings(rules: AxisRules, abstract_tree, axes_tree):
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, s),
        tree_specs(rules, abstract_tree, axes_tree),
        is_leaf=lambda x: isinstance(x, P),
    )
