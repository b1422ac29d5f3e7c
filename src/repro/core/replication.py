"""State-replication engine: training-state pytree ⇄ byte shards.

The paper replicates "model weights, optimizer states, and runtime info"
(§III, Fig 3). Here a JAX training-state pytree is flattened to a contiguous
byte view with a manifest; Algorithm 1/2 plans over the byte sizes; shards are
materialized (optionally int8-compressed), shipped (simulated or real), and
reassembled into an identical pytree on the joining node.

``plan_for_sharded_state`` handles TP/EP-sharded states (DESIGN.md §5): only
same-shard-rank neighbors are valid sources, so planning runs per rank group.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codec as wire_codec
from repro.core.plans import plan_assignment
from repro.core.sharding_alg import Assignment, NeighborLink
from repro.kernels import ops as kernel_ops
from repro.optim.compression import (
    Q_BLOCK,
    ROUNDTRIP_REL_SLACK,
    compressed_bytes,
    int8_dequantize,
    int8_quantize,
)


@dataclass(frozen=True)
class TensorEntry:
    path: str
    shape: Tuple[int, ...]
    dtype: str
    offset: int  # byte offset in the flat stream
    nbytes: int


@dataclass
class StateManifest:
    entries: List[TensorEntry]
    total_bytes: int
    treedef: object = None

    @property
    def tensor_sizes(self) -> List[int]:
        return [e.nbytes for e in self.entries]


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def build_manifest(tree) -> StateManifest:
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    entries = []
    off = 0
    for path, leaf in leaves:
        arr = np.asarray(leaf)
        e = TensorEntry(_path_str(path), arr.shape, str(arr.dtype), off, arr.nbytes)
        entries.append(e)
        off += arr.nbytes
    return StateManifest(entries, off, jax.tree_util.tree_structure(tree))


def flatten_state(tree) -> Tuple[np.ndarray, StateManifest]:
    """Concatenate all leaves into one uint8 stream + manifest."""
    manifest = build_manifest(tree)
    buf = np.empty(manifest.total_bytes, np.uint8)
    leaves = jax.tree_util.tree_leaves(tree)
    for e, leaf in zip(manifest.entries, leaves):
        arr = np.ascontiguousarray(np.asarray(leaf))
        buf[e.offset : e.offset + e.nbytes] = arr.view(np.uint8).reshape(-1)
    return buf, manifest


def unflatten_state(buf: np.ndarray, manifest: StateManifest):
    leaves = []
    for e in manifest.entries:
        raw = buf[e.offset : e.offset + e.nbytes]
        leaves.append(raw.view(np.dtype(e.dtype)).reshape(e.shape))
    return jax.tree_util.tree_unflatten(manifest.treedef, leaves)


# ---------------------------------------------------------------------------
# Shards.
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShardRange:
    index: int
    start: int
    end: int

    @property
    def nbytes(self) -> int:
        return self.end - self.start


def make_shard_ranges(total_bytes: int, shard_size: int) -> List[ShardRange]:
    out = []
    i = 0
    for start in range(0, total_bytes, shard_size):
        out.append(ShardRange(i, start, min(start + shard_size, total_bytes)))
        i += 1
    return out


def extract_shards(buf: np.ndarray, ranges: Sequence[ShardRange]) -> Dict[int, bytes]:
    return {r.index: buf[r.start : r.end].tobytes() for r in ranges}


def assemble_shards(shards: Dict[int, bytes], ranges: Sequence[ShardRange],
                    total_bytes: int) -> np.ndarray:
    buf = np.empty(total_bytes, np.uint8)
    seen = 0
    for r in ranges:
        data = shards[r.index]
        assert len(data) == r.nbytes, (r, len(data))
        buf[r.start : r.end] = np.frombuffer(data, np.uint8)
        seen += r.nbytes
    assert seen == total_bytes
    return buf


# ---------------------------------------------------------------------------
# Wire codec on real arrays (repro.core.codec is the cost model; this is the
# data path): fp32 leaves ship as int8 codes + per-block fp32 scales — the
# exact framing kernels/shard_codec.py produces on TPU, with
# optim/compression.int8_quantize as the bit-identical jnp reference on
# hosts. Non-fp32 leaves ship raw: the scale/2 error bound is an fp32
# contract (see int8_dequantize), and integer/bool runtime state must
# survive exactly.
# ---------------------------------------------------------------------------


@dataclass
class EncodedLeaf:
    """One tensor of an encoded state: either int8 codes + scales, or the
    raw array (non-fp32 dtypes, or the ``none`` codec)."""
    kind: str  # "int8" | "raw"
    payload_bytes: int
    wire_bytes: int
    codes: Optional[np.ndarray] = None
    scales: Optional[np.ndarray] = None
    meta: Optional[tuple] = None
    raw: Optional[np.ndarray] = None


def _kernel_encode_matches(xf_blocks, codes: np.ndarray,
                           scales: np.ndarray) -> None:
    """Run the Pallas shard codec on the padded block view and require it to
    be bit-identical to the jnp reference (codes AND scales)."""
    kc, ks = kernel_ops.shard_encode(xf_blocks)
    if not np.array_equal(np.asarray(kc), codes):
        raise AssertionError(
            "shard_encode kernel codes diverged from int8_quantize reference")
    if not np.array_equal(np.asarray(ks), scales):
        raise AssertionError(
            "shard_encode kernel scales diverged from int8_quantize reference")


def encode_state(tree, codec: str = wire_codec.CODEC_INT8,
                 *, verify_kernel: bool = True):
    """Encode a training-state pytree for the wire.

    Returns ``(leaves, manifest, total_wire_bytes)``. fp32 leaves are
    int8-block-quantized (one fp32 scale per ``Q_BLOCK`` elements); other
    dtypes ship raw. With ``verify_kernel`` the Pallas kernel re-encodes
    each quantized leaf and must match the reference bit-for-bit. Any
    non-``none`` codec quantizes the same way — top-k is a gradient-exchange
    refinement with no residual to absorb its error here, so replication
    state never drops elements (the simulator's int8+topk wire model applies
    to gradient-like payloads)."""
    manifest = build_manifest(tree)
    leaves = jax.tree_util.tree_leaves(tree)
    out: List[EncodedLeaf] = []
    total_wire = 0
    for entry, leaf in zip(manifest.entries, leaves):
        # ascontiguousarray promotes 0-d to (1,); reshape restores scalars.
        arr = np.ascontiguousarray(np.asarray(leaf)).reshape(entry.shape)
        if (codec != wire_codec.CODEC_NONE and arr.dtype == np.float32
                and arr.size):
            codes, scales, meta = int8_quantize(jnp.asarray(arr))
            codes, scales = np.asarray(codes), np.asarray(scales)
            if verify_kernel:
                pad = (-arr.size) % Q_BLOCK
                xf = np.pad(arr.reshape(-1), (0, pad)).reshape(-1, Q_BLOCK)
                _kernel_encode_matches(jnp.asarray(xf), codes, scales)
            wire = int(compressed_bytes(codes, scales))
            out.append(EncodedLeaf("int8", arr.nbytes, wire,
                                   codes=codes, scales=scales, meta=meta))
        else:
            wire = arr.nbytes
            out.append(EncodedLeaf("raw", arr.nbytes, wire, raw=arr))
        total_wire += wire
    return out, manifest, total_wire


def decode_state(leaves: Sequence[EncodedLeaf], manifest: StateManifest,
                 *, verify_kernel: bool = True):
    """Inverse of :func:`encode_state`: rebuild the pytree on the joining
    node. int8 leaves decode through ``int8_dequantize`` (fp32-exact
    ``code * scale``), with the Pallas decode kernel cross-checked
    bit-for-bit. Every decoded fp32 element satisfies
    ``|decoded - original| <= scale_of_its_block / 2``."""
    arrs = []
    for e in leaves:
        if e.kind == "raw":
            arrs.append(e.raw)
            continue
        dec = np.asarray(int8_dequantize(jnp.asarray(e.codes),
                                         jnp.asarray(e.scales), e.meta))
        if verify_kernel:
            kd = np.asarray(kernel_ops.shard_decode(jnp.asarray(e.codes),
                                                    jnp.asarray(e.scales)))
            if not np.array_equal(kd.reshape(-1)[:dec.size],
                                  dec.reshape(-1).astype(np.float32)):
                raise AssertionError(
                    "shard_decode kernel diverged from int8_dequantize")
        arrs.append(dec)
    return jax.tree_util.tree_unflatten(manifest.treedef, arrs)


def roundtrip_max_error_ok(tree, decoded_tree,
                           leaves: Sequence[EncodedLeaf]) -> bool:
    """Check the documented bound: every int8-encoded fp32 element is within
    ``scale/2`` of the original (raw leaves must match exactly). The bound
    gets ``ROUNDTRIP_REL_SLACK`` for fp32 rounding of the ``code * scale``
    reconstruction (see int8_dequantize's contract)."""
    orig = jax.tree_util.tree_leaves(tree)
    dec = jax.tree_util.tree_leaves(decoded_tree)
    for o, d, e in zip(orig, dec, leaves):
        o, d = np.asarray(o), np.asarray(d)
        if e.kind == "raw":
            if not np.array_equal(o, d):
                return False
            continue
        err = np.abs(o.astype(np.float32) - d.astype(np.float32)).reshape(-1)
        pad = (-err.size) % Q_BLOCK
        err = np.pad(err, (0, pad)).reshape(-1, Q_BLOCK)
        bound = np.asarray(e.scales)[:, None] / 2.0
        if not np.all(err <= bound * (1.0 + ROUNDTRIP_REL_SLACK)):
            return False
    return True


# ---------------------------------------------------------------------------
# End-to-end replication (used by the elastic runtime and tests).
# ---------------------------------------------------------------------------


@dataclass
class ReplicationExecution:
    assignment: Assignment
    ranges: List[ShardRange]
    manifest: StateManifest
    bytes_per_source: Dict[int, int]


def plan_replication(tree, neighbors: Dict[int, NeighborLink]) -> ReplicationExecution:
    """Plan shard pulls for a full training-state pytree (identical across
    sources — synchronous DP, the paper's setting)."""
    buf_manifest = build_manifest(tree)
    asg = plan_assignment(buf_manifest.tensor_sizes, neighbors)
    ranges = make_shard_ranges(buf_manifest.total_bytes, asg.shard_size)
    per_source = {
        u: sum(ranges[k].nbytes for k in ks if k < len(ranges))
        for u, ks in asg.shards_per_neighbor.items()
    }
    return ReplicationExecution(asg, ranges, buf_manifest, per_source)


def execute_replication(tree, plan: ReplicationExecution):
    """Materialize shards per source and reassemble — the actual data path a
    joining node runs; returns (reassembled_tree, shards_by_source)."""
    buf, manifest = flatten_state(tree)
    by_source: Dict[int, Dict[int, bytes]] = {}
    for u, ks in plan.assignment.shards_per_neighbor.items():
        rs = [plan.ranges[k] for k in ks if k < len(plan.ranges)]
        by_source[u] = extract_shards(buf, rs)
    merged: Dict[int, bytes] = {}
    for shards in by_source.values():
        merged.update(shards)
    out = assemble_shards(merged, plan.ranges, manifest.total_bytes)
    return unflatten_state(out, manifest), by_source


def plan_for_sharded_state(
    rank_of_neighbor: Dict[int, int],
    my_rank_sources: Dict[int, NeighborLink],
    tree,
) -> ReplicationExecution:
    """TP/EP-sharded training state: only neighbors holding the same shard
    rank are valid sources. Callers pass the same-rank neighbor subset; this
    is a thin wrapper documenting the grouping contract."""
    assert my_rank_sources, "no same-rank neighbors — fall back to checkpoint tier"
    return plan_replication(tree, my_rank_sources)
