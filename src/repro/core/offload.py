"""Host-offload primitives: the TPU-native incarnation of the paper's
Level-1/Level-2 transfer machinery.

On TPU, the asynchronous store/prefetch threads of the paper map onto XLA
async ``copy-start``/``copy-done`` pairs between HBM (``"device"``) and host
RAM (``"pinned_host"``), scheduled by the latency-hiding scheduler to overlap
with MXU compute.  JAX exposes this through

* ``checkpoint_name`` tags on intermediate values, and
* ``save_and_offload_only_these_names`` remat policies,

which together tell XLA *which* residuals of a rematerialised region live on
the host.  This module centralises those knobs.
"""
from __future__ import annotations

import functools
from typing import Any, Sequence

import jax
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding

# Residual-name vocabulary (shared with models/ and core/multistage_scan).
BOUNDARY = "ms_boundary"          # segment-boundary carry -> Level 2
INNER_BOUNDARY = "ms_inner"       # nested sub-segment boundary -> Level 1
LAYER_INPUT = "layer_input"       # transformer layer input activation
ATTN_OUT = "attn_out"
MLP_OUT = "mlp_out"
QKV = "qkv_proj"
FFN_PRE = "ffn_pre"

DEVICE = "device"
HOST = "pinned_host"

# ``jax.named_scope`` names of the offloaded step's parts.  They land in the
# ``op_name`` metadata of every op the part lowers to, which is how a device
# trace finds a part's time; within a segment, ``phase_of`` tells the sweeps
# apart.
SCOPE_SEGMENT = "chain.segment"     # every chain step of every segment
SCOPE_PRELUDE = "chain.prelude"     # the loss before the chain (embedding)
SCOPE_READOUT = "chain.readout"     # the loss after it (final norm, head)
SCOPE_OPTIMIZER = "optimizer"       # the update, clipping, grad_norm
SCOPE_SSD = "ssd"                   # the SSD scan of a Mamba-2 layer
SCOPES = (SCOPE_SEGMENT, SCOPE_PRELUDE, SCOPE_READOUT, SCOPE_OPTIMIZER,
          SCOPE_SSD)


def phase_of(op_name: str) -> str:
    """The sweep an op belongs to, from JAX's own markers in its ``op_name``
    metadata: a recomputed op sits under ``rematted_computation``, a
    backward op under ``transpose(``, a forward op under neither."""
    if "rematted_computation" in op_name:
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    return "forward"


def tag(x: Any, name: str) -> Any:
    """Tag every leaf of a pytree with a residual name (identity op)."""
    return jax.tree_util.tree_map(lambda v: checkpoint_name(v, name), x)


# ---------------------------------------------------------------------------
# Remat policies
# ---------------------------------------------------------------------------


def offload_policy(offload_names: Sequence[str],
                   save_names: Sequence[str] = ()) -> Any:
    """Save ``save_names`` in HBM, offload ``offload_names`` to pinned host
    memory, recompute everything else."""
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=list(save_names),
        names_which_can_be_offloaded=list(offload_names),
        offload_src=DEVICE,
        offload_dst=HOST,
    )


def save_policy(save_names: Sequence[str]) -> Any:
    """Save ``save_names`` in HBM, recompute everything else (single-stage)."""
    return jax.checkpoint_policies.save_only_these_names(*save_names)


def segment_policy(offload: bool, boundary_name: str = BOUNDARY) -> Any:
    """Per-segment remat policy for the trace-native scan engine: the
    segment-boundary carry goes to pinned host memory (the paper's Level-2
    store, compiled) when ``offload``, or stays in HBM (plain segmented
    remat) when the backend cannot lower host placement — see
    :func:`host_offload_supported`."""
    if offload:
        return offload_policy([boundary_name])
    return save_policy([boundary_name])


def _offload_plus(offload_pol, bool_pol):
    """Combine an Offloadable-returning policy with a boolean one —
    ``save_from_both_policies`` rejects mixed return types, and the
    name-based policies return a *truthy* RecomputeType sentinel for
    unmatched primitives, so only an explicit type check composes."""

    def policy(prim, *args, **kwargs):
        r = offload_pol(prim, *args, **kwargs)
        if type(r).__name__ == "RecomputeType":
            return bool_pol(prim, *args, **kwargs)
        return r

    return policy


_POLICIES = {
    # name -> thunk building the policy
    "none": lambda: jax.checkpoint_policies.everything_saveable,
    "full": lambda: jax.checkpoint_policies.nothing_saveable,
    "dots": lambda: jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch": lambda: jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    "save_boundary": lambda: save_policy([BOUNDARY]),
    "offload_boundary": lambda: offload_policy([BOUNDARY]),
    "offload_boundary_save_inner": lambda: offload_policy([BOUNDARY], [INNER_BOUNDARY]),
    "save_layer": lambda: save_policy([LAYER_INPUT]),
    "offload_layer": lambda: offload_policy([LAYER_INPUT]),
    "offload_layer_save_dots": lambda: _offload_plus(
        offload_policy([LAYER_INPUT]),
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
    ),
    "offload_layer_save_all_dots": lambda: _offload_plus(
        offload_policy([LAYER_INPUT]),
        jax.checkpoint_policies.dots_saveable,
    ),
    "offload_layer_save_attn": lambda: offload_policy([LAYER_INPUT], [ATTN_OUT]),
}


def make_policy(name: str) -> Any:
    try:
        return _POLICIES[name]()
    except KeyError:
        raise ValueError(
            f"unknown remat policy {name!r}; known: {sorted(_POLICIES)}"
        ) from None


def policy_names() -> Sequence[str]:
    return sorted(_POLICIES)


@functools.lru_cache(maxsize=1)
def host_offload_supported() -> bool:
    """Whether this backend/jaxlib lowers offload remat policies to host
    memory-space transfers.

    TPU runtimes do, and so does the CPU backend of current jaxlib; older
    CPU builds reject the ``TransferToMemoryKind`` placement.  Callers
    (the scan engine, platform-dependent tests) keep boundaries in device
    memory where this is False.  On a TPU a failing probe is a fault, not
    an answer, so it raises there.
    """
    import jax.numpy as jnp

    def f(x):
        x = checkpoint_name(x, LAYER_INPUT)
        return jnp.sum(jnp.tanh(x) ** 2)

    try:
        pol = make_policy("offload_layer")
        jaxpr = str(jax.make_jaxpr(
            jax.grad(jax.checkpoint(f, policy=pol)))(jnp.ones((2, 2))))
    except Exception:
        if jax.default_backend() == "tpu":
            raise
        return False
    return "<host>" in jaxpr


# ---------------------------------------------------------------------------
# Per-shard host transfer (the sharded Level-2 streams)
# ---------------------------------------------------------------------------


def local_shards(x: jax.Array) -> dict:
    """device -> host shard for one mesh-sharded array: each addressable
    shard copies out independently (``jax.device_get`` of the per-device
    buffer), so no global gather ever materialises on one host thread."""
    import numpy as np
    return {s.device: np.asarray(s.data) for s in x.addressable_shards}


def assemble_shards(shape, sharding: NamedSharding, parts: dict) -> jax.Array:
    """Inverse of :func:`local_shards`: commit each host shard back to its
    device and reassemble the global array under ``sharding`` — the
    ``NamedSharding`` recorded when the boundary was split."""
    arrays = [jax.device_put(part, dev) for dev, part in parts.items()]
    return jax.make_array_from_single_device_arrays(
        tuple(shape), sharding, arrays)
