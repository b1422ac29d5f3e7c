"""ElasticTrainer: live stop-free autoscaling over real JAX devices.

This is the paper's mechanism running on actual arrays (not the simulator):
synchronous data-parallel training over a device mesh that grows and shrinks
*without restarts*:

  * scale-out: a joining device gets the training state via a Chaos
    replication plan (Algorithm 1/2 over a synthetic per-device link model);
    physically the state moves chip to chip, never through the host
    (``ElasticTrainer._move``: a compiled relayout on the devices that hold
    a leaf where no device holds a new block whole, then
    ``jax.device_put``), and the plan's byte accounting (+ optional int8
    shard codec) is reported like the paper's Fig 7;
  * scale-in / failure: the mesh shrinks; state survives on the remaining
    replicas (synchronous DP ⇒ identical state — the paper's §III premise);
    a failed device additionally exercises the MemoryReplicaStore restore;
  * per-mesh-size compiled train steps are cached, so churn costs one
    compile the first time a given cluster size appears (then it's free);
    that compile is timed on its own (``compile_seconds``), apart from the
    step times;
  * each node brings its data split (paper §VI-A): the loader reshard hook
    is invoked on every membership change;
  * link events from replayed scenario traces (degrade / sever / restore)
    land on a per-device link-override table layered over ``link_model``,
    so a degraded link reshapes the replication plans of later scale-outs
    exactly as it does in the simulator;
  * one real-clock recorder (``tracer``, :class:`repro.core.telemetry.
    Recorder`) holds every timing: ``chaos.step`` (``put_batch``, ``run``,
    ``read_metrics``), ``chaos.compile``, ``chaos.move`` (``plan``,
    ``codec``, ``transfer``, ``on_reshard``) and the backend's
    ``chaos.handle``, with the counters ``chaos_compiles_total``,
    ``chaos_compile_seconds_total``, ``chaos_attention_sites_total``,
    ``chaos_move_bytes_total`` and ``chaos_move_leaves_total``;
  * on a TPU the step runs attention's core in the Pallas flash-attention
    kernel, forward and backward (``kernels/ops.flash_attention``), per
    shard on a mesh of several devices; elsewhere it takes the XLA path.

It runs on whatever devices it is given: TPU chips (``chip_smoke.py``), or
CPU devices under ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` for
a multi-device CPU demonstration (examples/elastic_training.py).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import codec as wire_codec
from repro.core.engine import MIN_LINK_MBPS, ChurnEngine, ChurnEvent, EventLedger
from repro.core.plans import (
    ParallelismPlan,
    ReshardPolicy,
)
from repro.core.recovery import FaultContext, decision_detail, make_policy
from repro.core.replication import (
    decode_state,
    encode_state,
    plan_replication,
    roundtrip_max_error_ok,
)
from repro.core.sharding_alg import NeighborLink
from repro.core.telemetry import Recorder
from repro.core.topology import MBPS
from repro.models.layers import attention_sites

#: per-byte transmission delay standing in for a severed link: the Alg-1/2
#: planner derates such a neighbor to (near) zero shards, so it drops out of
#: subsequent replication plans without ever making planning infeasible.
SEVERED_TRANS_S_PER_BYTE = 1.0


@dataclass
class ScaleEvent:
    kind: str
    device: str
    step: int
    #: seconds of the move's plan, codec and transfer spans (the data
    #: pipeline's ``on_reshard`` callback left out)
    wall_s: float
    plan_summary: Optional[dict] = None


def _box(index, shape) -> List[tuple]:
    return [sl.indices(dim)[:2] for sl, dim in zip(index, shape)]


def _per_leaf(leaves, target) -> list:
    """``target`` (a sharding, or a tree of them like the state) per leaf."""
    return ([target] * len(leaves) if isinstance(target, jax.sharding.Sharding)
            else jax.tree.leaves(target))


def received_bytes(state, target) -> int:
    """Bytes the devices of ``target`` (a sharding, or a tree of them like
    ``state``) must receive to hold ``state`` there: per leaf and device, the
    bytes of the block it holds afterwards less its overlap with the block
    that device held before. From the shardings' index maps alone; a leaf
    without a sharding (a host array) counts whole."""
    leaves = jax.tree.leaves(state)
    targets = _per_leaf(leaves, target)
    total = 0
    for leaf, new in zip(leaves, targets):
        shape = leaf.shape
        old = getattr(leaf, "sharding", None)
        held = old.devices_indices_map(shape) if old is not None else {}
        for dev, index in new.devices_indices_map(shape).items():
            block = _box(index, shape)
            want = int(np.prod([b - a for a, b in block]))
            if dev in held:
                have = [(max(a, c), min(b, d)) for (a, b), (c, d)
                        in zip(block, _box(held[dev], shape))]
                want -= int(np.prod([max(0, b - a) for a, b in have]))
            total += want * leaf.dtype.itemsize
    return total


def _blocks(sharding, shape) -> set:
    return {tuple((sl.start, sl.stop, sl.step) for sl in index)
            for index in sharding.devices_indices_map(shape).values()}


def relayout_sharding(old, new, shape):
    """The layout a leaf of ``shape`` held under ``old`` takes, on the devices
    that hold it, before ``jax.device_put`` puts it under ``new``; None where
    every block of ``new`` is a block of ``old``, whose buffer device_put
    then reuses or copies device to device. A block that no device holds
    whole is one device_put would assemble on the host (JAX 0.9's
    ``shard_sharded_device_array_slow_path`` reads the whole array back and
    uploads it again), so such a leaf is laid out anew first: as ``new``
    itself where the devices are the same (a reshard: a local slice, or an
    all-gather over the chips' links), else as a replica on ``old``'s
    devices, whose whole arrays serve ``new``'s replicas."""
    if _blocks(new, shape) <= _blocks(old, shape):
        return None
    if old.device_set == new.device_set:
        return new
    if not new.is_fully_replicated:
        raise ValueError("a move onto other devices lands on replicas")
    return NamedSharding(old.mesh, P())


def kernels_selected() -> bool:
    """Whether train steps run attention's core in the Pallas kernel: where
    JAX's default backend is a TPU (elsewhere the kernels would run in the
    interpreter). It selects attention's kernel alone: the recurrences of
    RWKV-6, Mamba-2 and Zamba2 (``kernels/ops.wkv6``, ``ops.ssd``) train on
    their XLA path on every backend, since their kernels have no per-shard
    path and no v5e compile test."""
    return jax.default_backend() == "tpu"


class ElasticTrainer:
    def __init__(self, model, *, devices: Optional[Sequence] = None,
                 initial: int = 2, per_device_batch: int = 2,
                 link_model: Optional[Callable[[int], NeighborLink]] = None,
                 on_reshard: Optional[Callable[[List[int]], None]] = None,
                 seed: int = 0, codec: str = wire_codec.CODEC_NONE):
        self.model = model
        #: wire codec for scale-out state movement ("none" / "int8" / ... —
        #: non-none policies int8-encode fp32 shard buffers through the
        #: Pallas codec path and report wire bytes; the *installed* state is
        #: always exact, since a lossy install would diverge the synchronous
        #: DP replicas the paper's §III premise relies on).
        self.codec = wire_codec.validate_policy(codec)
        self.pool = list(devices if devices is not None else jax.devices())
        assert initial <= len(self.pool)
        self.active: List = list(self.pool[:initial])
        self.per_device_batch = per_device_batch
        self.on_reshard = on_reshard
        self.link_model = link_model or (lambda i: NeighborLink(0.001, 1e-9, 0.0))
        # Trace link events override the static link model per device id
        # (degraded / severed / restored links), so replayed link churn
        # changes the plan shapes of subsequent scale-outs. Keyed per
        # (device, trace link) so overlapping impairments on one device
        # don't clobber each other; the slowest surviving impairment wins.
        self._link_overrides: Dict[int, Dict[object, NeighborLink]] = {}
        self._step_fns: Dict[tuple, Callable] = {}
        #: the executable behind each ``_step_fns`` entry, for its HLO text
        self._compiled: Dict[tuple, object] = {}
        #: per (from, to) layout of a move: the state leaves it lays out anew
        #: on their devices, and the compiled program that does it
        self._relayouts: Dict[tuple, tuple] = {}
        #: every timing of this trainer: spans on the host's real clock
        self.tracer = Recorder()
        # Current parallelism layout: tp-ways of tensor parallelism over the
        # active devices (1 = the pure-DP layout every pre-reshard trainer
        # ran — meshes, shardings and compiled steps are then bit-identical
        # to before) and the micro-batch split the reshard policy chose.
        self._tp = 1
        self._microbatch = 1
        self.step_count = 0
        self.events: List[ScaleEvent] = []
        self.state = None
        self._seed = seed
        # Recovery tiers (attach_recovery): the in-memory neighbor-replica
        # store (fast tier) and the async disk checkpointer (cold tier).
        # Both optional — a trainer without them behaves exactly as before.
        self.replica_store = None
        self.checkpointer = None
        self._replica_owner = 0

    # -- mesh / shardings ------------------------------------------------------

    @property
    def tp(self) -> int:
        return self._tp

    def parallelism_plan(self) -> ParallelismPlan:
        """The layout the trainer is currently running, as the same plan
        object the churn engine reasons about."""
        n = len(self.active)
        return ParallelismPlan((n // self._tp, self._tp),
                               devices=tuple(self.device_ids()),
                               microbatch=self._microbatch)

    def mesh(self) -> Mesh:
        if self._tp > 1:
            n = len(self.active)
            return Mesh(np.array(self.active).reshape(n // self._tp,
                                                      self._tp),
                        ("data", "model"))
        return Mesh(np.array(self.active), ("data",))

    def _state_sharding(self):
        """Replicated spec — the tp == 1 layout (kept as the single-sharding
        fast path; ``_state_shardings`` generalizes to tp > 1)."""
        return NamedSharding(self.mesh(), P())

    def _state_shardings(self, state=None):
        """Sharding (tree) for the training state under the current layout:
        tp == 1 replicates everything (one sharding broadcast over the
        tree — bit-identical to the pre-reshard path); tp > 1 shards each
        leaf's last dim over ``model`` when divisible, degrading
        non-divisible leaves to replication exactly like
        ``models.sharding._div`` (and the step-time model's
        ``replicated_fraction``)."""
        if self._tp == 1:
            return self._state_sharding()
        mesh = self.mesh()
        state = self.state if state is None else state

        def one(leaf):
            shape = getattr(leaf, "shape", ())
            if len(shape) and shape[-1] % self._tp == 0:
                return NamedSharding(
                    mesh, P(*([None] * (len(shape) - 1)), "model"))
            return NamedSharding(mesh, P())

        return jax.tree.map(one, state)

    def _batch_sharding(self):
        return NamedSharding(self.mesh(), P("data"))

    @property
    def global_batch(self) -> int:
        return self.per_device_batch * len(self.active)

    def device_ids(self) -> List[int]:
        return [d.id for d in self.active]

    # -- per-device link model (trace link events land here) --------------------

    def effective_link(self, device_id: int) -> NeighborLink:
        """The link the planner sees for ``device_id``: the slowest
        trace-applied override still in force (a device with both a severed
        and a degraded link is as bad as its worst impairment), or the
        static link model when no override remains."""
        ovs = self._link_overrides.get(device_id)
        if not ovs:
            return self.link_model(device_id)
        return max(ovs.values(), key=lambda nl: nl.trans_s_per_byte)

    def replication_neighbors(self) -> Dict[int, NeighborLink]:
        """Measured neighbor set a joining device plans over — every active
        device through its *effective* link (monitor §IV-A stand-in)."""
        return {d.id: self.effective_link(d.id) for d in self.active}

    def apply_link_event(self, kind: str, device_ids: Sequence[int],
                         bandwidth_mbps: Optional[float] = None,
                         latency_s: Optional[float] = None,
                         link: Optional[Sequence[int]] = None,
                         loss_rate: Optional[float] = None):
        """Map a trace link event onto the per-device link model.

        Host-simulated devices share one interconnect, so a trace link
        (u, v) is projected onto its endpoint devices: each named device's
        link toward future joiners is degraded (``link-degrade``), severed
        (``link-failure`` / ``link-leave``), or restored (``link-join`` —
        with new parameters when given, else clearing that link's
        impairment). Impairments are tracked per (device, trace link), so
        restoring one link never erases another link's still-active sever
        or degrade on the same device; :meth:`effective_link` surfaces the
        slowest survivor. Subsequent scale-out plans are built over the
        updated links, which is how severed or slow links change plan
        shapes during replay."""
        key = tuple(sorted(link)) if link is not None else None
        # Zero/negative rates would divide-by-zero; clamp to the same floor
        # the sim backend uses (severing is link-failure's job).
        if bandwidth_mbps is not None:
            bandwidth_mbps = max(float(bandwidth_mbps), MIN_LINK_MBPS)
        for did in device_ids:
            base = self.link_model(did)
            ovs = self._link_overrides.setdefault(did, {})
            if kind == "link-join":
                if bandwidth_mbps is None:
                    ovs.pop(key, None)
                else:
                    ovs[key] = NeighborLink(
                        latency_s if latency_s is not None else base.prop_s,
                        1.0 / (bandwidth_mbps * MBPS), base.sync_s)
            elif kind == "link-degrade":
                cur = ovs.get(key, base)
                trans = (1.0 / (bandwidth_mbps * MBPS)
                         if bandwidth_mbps is not None
                         else cur.trans_s_per_byte)
                ovs[key] = NeighborLink(
                    latency_s if latency_s is not None else cur.prop_s,
                    trans, cur.sync_s)
            elif kind in ("link-leave", "link-failure", "link-fault"):
                ovs[key] = NeighborLink(
                    base.prop_s, SEVERED_TRANS_S_PER_BYTE, base.sync_s)
            elif kind == "link-loss":
                # Lossy link: retransmissions inflate the effective per-byte
                # time by 1/(1-loss) — the goodput model SimBackend charges
                # on the simulated network. A missing rate means total loss;
                # at rate >= 1.0 the link is physically a blackhole, so it
                # is severed outright — exactly what probe detection does to
                # it on the simulator, keeping detected-mode traces diffable
                # across substrates instead of leaving a ~100x-slow zombie.
                rate = 1.0 if loss_rate is None else float(loss_rate)
                rate = min(max(rate, 0.0), 1.0)
                if rate >= 1.0:
                    ovs[key] = NeighborLink(
                        base.prop_s, SEVERED_TRANS_S_PER_BYTE, base.sync_s)
                else:
                    cur = ovs.get(key, base)
                    ovs[key] = NeighborLink(
                        cur.prop_s, cur.trans_s_per_byte / (1.0 - rate),
                        cur.sync_s)
            else:
                raise ValueError(f"not a link event kind: {kind!r}")

    # -- lifecycle ---------------------------------------------------------------

    def init(self, key=None):
        key = key if key is not None else jax.random.PRNGKey(self._seed)
        state = self.model.init_train_state(key)
        self.state = jax.device_put(state, self._state_sharding())
        if self.on_reshard:
            self.on_reshard(self.device_ids())
        return self.state

    @property
    def compile_seconds(self) -> Dict[tuple, float]:
        """Seconds spent compiling the train step, per (n, tp)."""
        return {(int(n), int(tp)): s for (n, tp), s in
                self.tracer.counter("chaos_compile_seconds_total").items()}

    def _abstract_mesh(self):
        """The context of a step's trace and of each of its calls: the
        layout's abstract mesh, which the attention op reads to run its
        kernel per shard. It names no devices, so a layout's step serves
        every later set of n devices (a concrete mesh would have to match
        the devices the step was compiled for)."""
        return jax.sharding.use_abstract_mesh(self.mesh().abstract_mesh)

    def _get_step_fn(self, n: int, batch):
        """The jitted step for ``(n, tp)``, compiled ahead of its first call
        (the call then reuses that executable) inside a ``chaos.compile``
        span, so that the compile is timed apart from the step. The step
        runs attention's core in the Pallas kernel where the backend is a
        TPU; the span's ``attention`` attribute and the counter
        ``chaos_attention_sites_total`` say which implementation the traced
        attention sites took. The layout's abstract mesh is set while
        tracing (and in ``step``), so the kernel runs per shard on a mesh of
        several devices."""
        key = (n, self._tp)
        if key not in self._step_fns:
            # Asked only where selected: a model without a kernel path keeps
            # the argument-free make_train_step() elsewhere.
            kw = {"use_pallas": "attention"} if kernels_selected() else {}
            step = self.model.make_train_step(**kw)
            state_sh = self._state_shardings()
            fn = jax.jit(
                step,
                in_shardings=(state_sh, self._batch_sharding()),
                out_shardings=(state_sh, None),
            )
            with self.tracer.span("chaos.compile", n=n, tp=self._tp) as sp, \
                    attention_sites() as sites, self._abstract_mesh():
                self._compiled[key] = fn.lower(self.state, batch).compile()
                impls = sorted(set(sites))
                sp.attrs["attention"] = (
                    impls[0] if len(impls) == 1 else
                    "mixed" if impls else "none")
            for impl in impls:
                self.tracer.count("chaos_attention_sites_total",
                                  sites.count(impl),
                                  help_text="Traced attention sites by "
                                            "implementation and layout",
                                  impl=impl, n=n, tp=self._tp)
            self.tracer.count("chaos_compiles_total",
                              help_text="Train-step compiles by layout",
                              n=n, tp=self._tp)
            self.tracer.count("chaos_compile_seconds_total", sp.duration_s,
                              help_text="Seconds compiling the train step",
                              n=n, tp=self._tp)
            self._step_fns[key] = fn
        return self._step_fns[key]

    def step(self, batch: dict):
        """batch arrays lead with global_batch (= per_device × n_active).
        One ``chaos.step`` span: ``put_batch`` (the batch onto the mesh),
        ``run`` (dispatch until the new state is ready on the devices; the
        step time, without the compile) and ``read_metrics``."""
        n = len(self.active)
        rows = int(jax.tree.leaves(batch)[0].shape[0])
        tr = self.tracer
        with tr.span("chaos.step", n=n, tp=self._tp, rows=rows):
            with tr.span("chaos.step.put_batch"):
                batch = jax.device_put(batch, self._batch_sharding())
            fn = self._get_step_fn(n, batch)
            with tr.span("chaos.step.run"), self._abstract_mesh():
                self.state, metrics = fn(self.state, batch)
                jax.block_until_ready(self.state)
            with tr.span("chaos.step.read_metrics"):
                metrics = jax.tree.map(float, metrics)
        self.step_count += 1
        return metrics

    # -- state movement ---------------------------------------------------------------

    def _relayout(self, key: tuple, sharding):
        """The leaves of the state (flat indices) that a move onto
        ``sharding`` (one, or a tree of them) lays out anew on their devices
        (``relayout_sharding``), and the program that does it (None where
        there are none): one per ``key``, the (from, to) layouts, compiled
        ahead of its first call. It donates nothing, and it is not a
        ``chaos.compile``, which counts train steps."""
        if key not in self._relayouts:
            leaves = jax.tree.leaves(self.state)
            staged = {}
            for i, (x, new) in enumerate(zip(leaves, _per_leaf(leaves,
                                                               sharding))):
                s = relayout_sharding(x.sharding, new, x.shape)
                if s is not None:
                    staged[i] = s
            prog = None
            if staged:
                prog = jax.jit(lambda xs: xs,
                               out_shardings=list(staged.values())).lower(
                    [leaves[i] for i in staged]).compile()
            self._relayouts[key] = (list(staged), prog)
        return self._relayouts[key]

    def _move(self, kind: str, target, prepare, codec=None):
        """Moves the state onto a new layout in one ``chaos.move`` span:
        ``codec`` (the ``codec`` callable, when given), ``plan``
        (``prepare``, which switches the trainer to the new layout, then
        ``target()``, its sharding or tree of them, the bytes the devices
        receive, and the relayout program, compiled on the pair's first
        move), ``transfer`` (the relayout of the leaves whose new blocks no
        device holds whole, on the devices that hold them, then
        ``device_put`` from device buffers, until ready; nothing passes
        through the host), ``on_reshard``. The counter
        ``chaos_move_leaves_total{kind,path}`` counts the leaves that took
        each path. Returns (``prepare``'s result, the codec's, the bytes,
        codec + plan + transfer seconds)."""
        tr = self.tracer
        old = (tuple(self.device_ids()), self._tp)
        with tr.span("chaos.move", kind=kind,
                     **{"from": (len(self.active), self._tp)}) as mv:
            spans, coded = [], None
            if codec is not None:
                with tr.span("chaos.move.codec") as sp:
                    coded = codec()
                spans.append(sp)
            with tr.span("chaos.move.plan") as sp:
                prepared = prepare()
                sharding = target()
                moved = received_bytes(self.state, sharding)
                picks, relayout = self._relayout(
                    (old, (tuple(self.device_ids()), self._tp)), sharding)
            spans.append(sp)
            with tr.span("chaos.move.transfer") as sp:
                leaves, tree = jax.tree.flatten(self.state)
                if picks:
                    laid = relayout([leaves[i] for i in picks])
                    for i, x in zip(picks, laid):
                        leaves[i] = x
                self.state = jax.device_put(tree.unflatten(leaves), sharding)
                jax.block_until_ready(self.state)
            spans.append(sp)
            mv.attrs.update(to=(len(self.active), self._tp), bytes=moved)
            if self.on_reshard:
                with tr.span("chaos.move.on_reshard"):
                    self.on_reshard(self.device_ids())
        tr.count("chaos_move_bytes_total", moved,
                 help_text="Bytes devices received on layout changes",
                 kind=kind)
        for path, n in (("relayout", len(picks)),
                        ("copy", len(leaves) - len(picks))):
            tr.count("chaos_move_leaves_total", n,
                     help_text="State leaves moved on layout changes, by "
                               "path: laid out anew on their devices, or "
                               "copied from whole blocks",
                     kind=kind, path=path)
        return prepared, coded, moved, sum(x.duration_s for x in spans)

    # -- elasticity -----------------------------------------------------------------

    def scale_out(self, device=None, codec: Optional[str] = None) -> ScaleEvent:
        """Stop-free join: plan shard pulls with Chaos, move state onto the
        enlarged mesh, reshard the data pipeline. No checkpoint, no restart.

        Under a non-``none`` codec (standing policy or per-call override)
        the fp32 state buffers are int8-encoded and decoded through the
        shard codec (the Pallas kernel, required to match the jnp reference
        bit for bit) to account wire bytes and validate the ``scale/2``
        round-trip bound; the state installed on the mesh stays exact."""
        eff_codec = self.codec if codec is None else wire_codec.validate_policy(codec)
        candidates = [d for d in self.pool if d not in self.active]
        if device is None:
            if not candidates:
                raise RuntimeError("device pool exhausted")
            device = candidates[0]

        def prepare():
            # Chaos plan over current members as neighbors of the joining
            # device, through their effective (possibly degraded/severed)
            # links. Membership change resets the layout to the
            # replicate-only baseline (tp = 1); a reshard policy re-applies
            # tensor parallelism via apply_reshard.
            plan = plan_replication(self.state, self.replication_neighbors())
            self._tp = 1
            self._microbatch = 1
            self.active = self.active + [device]
            return plan

        def run_codec():
            enc, manifest, wire = encode_state(self.state, eff_codec,
                                               verify_kernel=True)
            decoded = decode_state(enc, manifest, verify_kernel=True)
            assert roundtrip_max_error_ok(self.state, decoded, enc), \
                "shard codec round-trip exceeded the scale/2 error bound"
            return {
                "codec": eff_codec,
                "payload_bytes": int(manifest.total_bytes),
                "wire_bytes": int(wire),
                "wire_reduction": (float(manifest.total_bytes) / wire
                                   if wire else 1.0),
            }

        plan, codec_summary, moved, wall = self._move(
            "scale-out", self._state_sharding, prepare,
            run_codec if eff_codec != wire_codec.CODEC_NONE else None)
        summary = {
            "shard_size": plan.assignment.shard_size,
            "n_shards": plan.assignment.n_shards,
            "bytes_per_source": plan.bytes_per_source,
            "predicted_completion_s": plan.assignment.completion_s,
            "bytes_moved": moved,
        }
        if codec_summary is not None:
            summary["codec"] = codec_summary
        ev = ScaleEvent("scale-out", str(device), self.step_count, wall,
                        summary)
        self.events.append(ev)
        return ev

    def scale_in(self, device=None, failure: bool = False) -> ScaleEvent:
        """Node leaves/fails: shrink the mesh; state survives on remaining
        replicas (synchronous DP). Stop-free — next step recompiles at most."""
        if device is None:
            device = self.active[-1]
        if len(self.active) <= 1:
            raise RuntimeError("cannot scale below one device")
        # The move gathers any tp-sharded leaves back to full replicas on
        # the old mesh, the leaving device included, before dropping it; the
        # survivors keep their replicas (the replicate-only baseline). A
        # reshard policy re-applies tensor parallelism afterwards.
        survivors = [d for d in self.active if d != device]
        kind = "node-failure" if failure else "scale-in"

        def prepare():
            self._tp = 1
            self._microbatch = 1
            self.active = survivors

        _, _, moved, wall = self._move(kind, self._state_sharding, prepare)
        ev = ScaleEvent(kind, str(device), self.step_count, wall,
                        {"bytes_moved": moved})
        self.events.append(ev)
        return ev

    def apply_reshard(self, tp: int, microbatch: int = 1) -> ScaleEvent:
        """Apply a parallelism-plan change on real arrays: rebuild the mesh
        at (dp, tp) and move every state leaf from its current
        ``NamedSharding`` to the new layout's on the same devices, in one
        compiled program: a dp → tp reshard slices replicas in place and the
        reverse all-gathers — both bit-identical round trips (tests mark the
        real-array version ``slow``); leaves whose blocks stay whole keep
        their buffers. Stop-free: the next step compiles at most once per
        (n, tp)."""
        tp = int(tp)
        n = len(self.active)
        if tp < 1 or n % tp:
            raise ValueError(f"tp={tp} does not divide {n} active devices")

        def prepare():
            self._tp = tp
            self._microbatch = max(1, int(microbatch))

        _, _, moved, wall = self._move("reshard", self._state_shardings,
                                       prepare)
        ev = ScaleEvent("reshard", str(self.active[0]), self.step_count,
                        wall, {"shape": [n // tp, tp],
                               "microbatch": self._microbatch,
                               "bytes_moved": moved})
        self.events.append(ev)
        return ev

    # -- recovery tiers (repro.checkpoint wired into the live trainer) ---------

    def attach_recovery(self, *, replica_store=None, checkpointer=None,
                        owner: int = 0):
        """Wire the checkpoint layer in: a
        :class:`~repro.checkpoint.memory_ckpt.MemoryReplicaStore` (fast
        tier — neighbor replicas, sub-second restore) and/or an
        :class:`~repro.checkpoint.async_ckpt.AsyncCheckpointer` (cold tier —
        durable disk). ``owner`` keys the replica set (the coordinator's
        trace node id)."""
        self.replica_store = replica_store
        self.checkpointer = checkpointer
        self._replica_owner = int(owner)

    def checkpoint(self, step: Optional[int] = None) -> dict:
        """Push the current training state to every attached tier.

        One host snapshot feeds both: the replica store shards it across the
        active devices' effective links (Alg 1/2 balanced), the async
        checkpointer writes it to disk off-thread. Returns which tiers took
        the push — both restore paths must reproduce this state
        bit-identically (tests/test_checkpoint_churn.py)."""
        if self.replica_store is None and self.checkpointer is None:
            raise RuntimeError("no recovery tier attached (attach_recovery)")
        step = self.step_count if step is None else int(step)
        host = jax.tree.map(np.asarray, self.state)
        tiers = []
        if self.replica_store is not None:
            self.replica_store.push(self._replica_owner, step, host,
                                    self.replication_neighbors())
            tiers.append("replica")
        if self.checkpointer is not None:
            self.checkpointer.save(step, host)
            tiers.append("checkpoint")
        return {"step": step, "tiers": tiers}

    def restore_from(self, tier: str) -> int:
        """Reinstall training state from a recovery tier ("replica" or
        "checkpoint"); returns the restored step. Both tiers round-trip the
        exact bytes the matching :meth:`checkpoint` pushed, so A/B-ing them
        must land bit-identical state."""
        if tier == "replica":
            if self.replica_store is None:
                raise RuntimeError("no replica store attached")
            tree, step = self.replica_store.restore(self._replica_owner)
        elif tier == "checkpoint":
            if self.checkpointer is None:
                raise RuntimeError("no checkpointer attached")
            self.checkpointer.wait()  # async writes must land before reads
            tree, step = self.checkpointer.restore_latest(self.state)
            if tree is None:
                raise RuntimeError("no checkpoint on disk")
        else:
            raise ValueError(f"unknown recovery tier {tier!r}")
        self.state = jax.device_put(tree, self._state_shardings(tree))
        return step

    # -- scenario replay (the unified churn pipeline) ---------------------------------

    def replay_scenario(self, events, *, batch_fn=None, steps_between: int = 1,
                        min_active: int = 2, reshard: str = "never",
                        reshard_policy: Optional[ReshardPolicy] = None,
                        state_bytes: int = 0,
                        tensor_sizes: Optional[Sequence[int]] = None,
                        policy="fixed",
                        ) -> EventLedger:
        """Drive this trainer with a churn trace through the same
        :class:`~repro.core.engine.ChurnEngine` pipeline the simulator uses.
        ``policy`` selects the recovery policy (``repro.core.recovery``) —
        the same spec handed to ``SimBackend`` yields the same decisions on
        the same trace. Returns the event ledger; per-event wall times land
        in ``self.events`` (ScaleEvent list) as before."""
        engine = ChurnEngine(TrainerBackend(self, batch_fn=batch_fn,
                                            steps_between=steps_between,
                                            min_active=min_active,
                                            reshard=reshard,
                                            reshard_policy=reshard_policy,
                                            state_bytes=state_bytes,
                                            tensor_sizes=tensor_sizes,
                                            policy=policy))
        return engine.run(events)

    def metrics_snapshot(self) -> dict:
        """Point-in-time read of training observables for telemetry scrapes
        (repro.core.telemetry). Pure read; step times are the
        ``chaos.step.run`` spans still in the recorder's ring, raw, by the
        active device count of their ``chaos.step`` — histogram bucketing is
        the registry's job."""
        steps = {r.seq: r for r in self.tracer.named("chaos.step")}
        times: Dict[int, list] = {}
        for r in self.tracer.named("chaos.step.run"):
            times.setdefault(steps[r.parent].attrs["n"], []).append(
                r.duration_s)
        return {
            "n_active": len(self.active),
            "step_count": self.step_count,
            "step_times": dict(sorted(times.items())),
        }


# ---------------------------------------------------------------------------
# Churn-engine backend: the same trace files the simulator replays drive a
# live ElasticTrainer on real JAX devices.
# ---------------------------------------------------------------------------


class TrainerBackend:
    """Executes churn events on an :class:`ElasticTrainer`.

    Real hardware applies events sequentially (there is no virtual clock to
    overlap on), but the pipeline, the trace format, and the ledger are
    shared with :class:`~repro.core.engine.SimBackend` — one scenario file
    exercises the protocol in simulation *and* on real arrays. Ledger
    records carry only deterministic fields (device ids, step indices, plan
    shapes); wall-clock timings stay in ``trainer.events``.

    Link events resolve their endpoints to devices (via the trace-node map,
    falling back to matching pool device ids) and are applied through
    :meth:`ElasticTrainer.apply_link_event`, so degraded or severed links
    change the plan shapes of later joins; events whose endpoints resolve to
    no device stay ``noop-link`` for trace parity.

    Fault kinds route like their detected outcomes: there is no virtual
    clock to sweep on, so the trainer's monitor stand-in "detects" at the
    next event boundary — ``node-fault`` scales the device in as a failure,
    ``link-fault`` severs the per-device link, ``link-loss`` inflates the
    link's effective per-byte time by the goodput factor. Ledger records
    keep the fault kind and mark ``detected`` so detected-mode traces stay
    diffable across substrates.
    """

    def __init__(self, trainer: ElasticTrainer, *, batch_fn=None,
                 steps_between: int = 1, min_active: int = 2,
                 reshard: str = "never",
                 reshard_policy: Optional[ReshardPolicy] = None,
                 state_bytes: int = 0,
                 tensor_sizes: Optional[Sequence[int]] = None,
                 policy="fixed"):
        self.trainer = trainer
        #: the trainer's recorder (a trainer double without one gets its own)
        self.tracer = getattr(trainer, "tracer", None) or Recorder()
        self.batch_fn = batch_fn
        self.steps_between = steps_between
        self.min_active = min_active
        self.results: Dict[int, object] = {}
        self._node_device: Dict[int, object] = {}  # trace node id -> device
        self._departed: set = set()  # trace nodes that already left/failed
        self._link_faulted: set = set()  # trace links with an applied fault
        # Unified recovery policy: the trainer backend runs the *same* pure
        # decision layer as SimBackend (repro.core.recovery over trace
        # membership + byte counts), so one trace yields identical
        # ``recovery-decided`` / reshard decisions on both substrates
        # (``recovery.decision_digest`` pins the parity); the chosen tp is
        # then applied on real arrays when it divides the live device
        # count. ``state_bytes`` / ``tensor_sizes`` parameterize the shared
        # step-time model — pass the simulated cluster's values for
        # cross-substrate parity.
        self.policy = make_policy(policy, reshard=reshard,
                                  reshard_policy=reshard_policy,
                                  state_bytes=int(state_bytes) or 1)
        self.degraded = False
        self.state_bytes = int(state_bytes)
        self.tensor_sizes = list(tensor_sizes or ())
        self.plan: Optional[ParallelismPlan] = None
        #: trace-level membership (node ids), mirroring the simulator's
        #: ``topo.active_nodes()`` — the decision input that must match.
        self._members = {d.id for d in trainer.active}
        #: device standing in for the scheduler/coordinator (defaults to
        #: the lowest-id active device — the simulator's home convention);
        #: a replayed ``scheduler-fault`` moves this, keeping one trace
        #: file runnable on both substrates.
        self._coordinator = None

    # -- engine protocol -----------------------------------------------------

    def advance_to(self, t: float, ledger: EventLedger):
        if self.batch_fn is None:
            return
        for _ in range(self.steps_between):
            self.trainer.step(self.batch_fn())

    def metrics_snapshot(self) -> Dict:
        """Backend-level telemetry snapshot, mirroring
        ``SimBackend.metrics_snapshot``'s shape where both substrates have
        the observable. Pure read."""
        return {
            "n_active": len(self.trainer.active),
            "degraded": self.degraded,
            "members": sorted(self._members, key=str),
        }

    def coordinator_device(self):
        """The device currently playing scheduler: the explicitly installed
        one while it remains active, else the lowest-id active device."""
        tr = self.trainer
        if self._coordinator is not None and self._coordinator in tr.active:
            return self._coordinator
        return min(tr.active, key=lambda d: d.id) if tr.active else None

    def handle(self, seq: int, ev: ChurnEvent, ledger: EventLedger):
        """One event in a ``chaos.handle`` span: the engine's backend and
        the recovery policy, with any state moves as child spans."""
        with self.tracer.span("chaos.handle", kind=ev.kind, seq=seq):
            self._handle(seq, ev, ledger)

    def _handle(self, seq: int, ev: ChurnEvent, ledger: EventLedger):
        tr = self.trainer
        if ev.kind == "scheduler-fault":
            # Coordinator swap, trainer-side: no virtual clock to elect on,
            # so the fail-over resolves at the event boundary — the dead
            # coordinator's device is shed (it failed silently) and the
            # deterministic successor (trace preference first, else lowest
            # remaining device id) takes the role. Training state survives
            # on the replicas; the next step recompiles at most.
            old = self.coordinator_device()
            if ev.node is not None and (old is None
                                        or self._device_for(ev.node)
                                        is not old):
                # Mirror SimBackend: a fault naming a non-current home
                # (e.g. re-killing the original scheduler after an earlier
                # fail-over moved the role) is skipped on both substrates.
                ledger.append(seq, ev.t, ev.kind, ev.node,
                              "skipped-not-scheduler",
                              {"home": old.id if old else None})
                return
            cands = sorted((d for d in tr.active if d is not old),
                           key=lambda d: d.id)
            if old is None or not cands:
                ledger.append(seq, ev.t, ev.kind, ev.node,
                              "skipped-no-deputy")
                return
            preferred = self._device_for(ev.new_home)
            new = (preferred if preferred is not None and preferred in cands
                   else cands[0])
            shed = False
            if len(tr.active) > self.min_active:
                sev = tr.scale_in(old, failure=True)
                self.results[seq] = sev
                shed = True
                self._members.discard(old.id)
            self._coordinator = new
            ledger.append(seq, ev.t, ev.kind, (old.id, new.id), "failover", {
                "old_home": old.id, "new_home": new.id, "shed": shed,
                "n_active": len(tr.active), "detected": True,
            })
            return
        if ev.kind == "checkpoint":
            # Trace-borne checkpoint request, mirroring SimBackend: push to
            # the attached recovery tiers now, or acknowledge with a
            # terminal skip so the trace stays diffable across substrates.
            # getattr: trainer doubles in older tests predate the tiers.
            coord = self.coordinator_device()
            subject = (ev.node if ev.node is not None
                       else (coord.id if coord is not None else -1))
            if (getattr(tr, "replica_store", None) is None
                    and getattr(tr, "checkpointer", None) is None):
                ledger.append(seq, ev.t, ev.kind, subject,
                              "ckpt-skipped-no-checkpointer")
                return
            info = tr.checkpoint()
            ledger.append(seq, ev.t, ev.kind, subject, "ckpt-saved",
                          {"step": info["step"], "tiers": info["tiers"]})
            return
        if ev.kind == "join":
            free = [d for d in tr.pool if d not in tr.active]
            if not free:
                ledger.append(seq, ev.t, ev.kind, ev.node, "skipped-pool-exhausted")
                return
            device = free[0]
            # Pass codec only when the event carries one: trainer doubles
            # (tests' fakes) may predate the kwarg, and an absent field
            # must leave the trainer's standing policy untouched.
            if ev.codec is None:
                sev = tr.scale_out(device)
            else:
                sev = tr.scale_out(device, codec=ev.codec)
            # The device may be a reuse of one an earlier trace node shed;
            # purge stale mappings so later events can't mis-target it.
            self._node_device = {n: d for n, d in self._node_device.items()
                                 if d is not device}
            self._node_device[ev.node] = device
            self._departed.discard(ev.node)
            self.results[seq] = sev
            detail = {
                "device": device.id, "step": sev.step,
                "n_active": len(tr.active),
                "n_shards": sev.plan_summary["n_shards"],
                "shard_size": sev.plan_summary["shard_size"],
            }
            # Codec wire accounting rides the ledger only when a codec was
            # active — codec-none traces stay byte-identical across PRs.
            if "codec" in sev.plan_summary:
                cs = sev.plan_summary["codec"]
                detail["codec"] = cs["codec"]
                detail["wire_bytes"] = cs["wire_bytes"]
            ledger.append(seq, ev.t, ev.kind, ev.node, "scale-out", detail)
            self._members.add(ev.node)
            self._maybe_reshard(seq, ev, ledger)
            return
        if ev.kind in ("leave", "node-failure", "node-fault"):
            failure = ev.kind in ("node-failure", "node-fault")
            detected = ev.kind == "node-fault"
            if ev.node in self._departed:  # duplicate departure in the trace
                ledger.append(seq, ev.t, ev.kind, ev.node, "skipped-not-active")
                return
            if len(tr.active) <= self.min_active:
                ledger.append(seq, ev.t, ev.kind, ev.node, "skipped-min-cluster")
                return
            device = self._node_device.get(ev.node)
            if device is not None and device not in tr.active:
                ledger.append(seq, ev.t, ev.kind, ev.node, "skipped-not-active")
                return
            if device is None:
                # Unmapped trace node: deterministically shed the newest
                # device that isn't standing in for a mapped trace node
                # (pool order is stable).
                mapped_live = {d for d in self._node_device.values()
                               if d in tr.active}
                cands = [d for d in tr.active if d not in mapped_live]
                device = (cands or tr.active)[-1]
            sev = tr.scale_in(device, failure=failure)
            self._node_device[ev.node] = device
            self._departed.add(ev.node)
            self.results[seq] = sev
            detail = {"device": device.id, "step": sev.step,
                      "n_active": len(tr.active)}
            if detected:
                detail["detected"] = True
            ledger.append(seq, ev.t, ev.kind, ev.node,
                          "node-failed" if failure else "scaled-in", detail)
            self._members.discard(ev.node if ev.node in self._members
                                  else device.id)
            if failure:
                # The same per-fault-class selection SimBackend runs: build
                # the substrate-independent context fields, decide, record.
                # Execution differs by substrate (state already lives on
                # the surviving replicas here; there is no wire to restore
                # over), but the *choice* — what decision_digest projects —
                # must match the simulator's.
                ctx = FaultContext(
                    kind="node-failure", t=ev.t, subject=(ev.node,),
                    n_active=len(tr.active), min_active=self.min_active,
                    state_bytes=self.state_bytes,
                    replica_feasible=(self.plan is None or self.plan.dp > 1),
                    ckpt_available=(getattr(tr, "checkpointer", None)
                                    is not None),
                    override=ev.recovery)
                dec = self.policy.decide(ctx)
                self._record_decision(seq, ev.t, ledger, ctx, dec)
                if dec.action == "park-and-degrade":
                    # No restore: train on without the dead device's
                    # redundancy. Terminal record mirrors the simulator's.
                    self.degraded = True
                    ledger.append(seq, ev.t, "recovery", ev.node,
                                  "parked-degraded",
                                  {"n_active": len(tr.active)})
            self._maybe_reshard(seq, ev, ledger)
            return
        # Link events: project the trace link onto its endpoint devices'
        # per-device link model. Unresolvable endpoints keep the historical
        # noop-link acknowledgement for trace parity.
        dev_ids = sorted({d.id for d in (self._device_for(ev.u),
                                         self._device_for(ev.v))
                          if d is not None and d in tr.active})
        if not dev_ids:
            ledger.append(seq, ev.t, ev.kind, (ev.u, ev.v), "noop-link")
            return
        link_key = (min(ev.u, ev.v), max(ev.u, ev.v))
        if ev.kind in ("link-fault", "link-loss"):
            # Mirror SimBackend's duplicate-fault dedup: re-applying a loss
            # factor would compound 1/(1-loss) and diverge the substrates.
            if link_key in self._link_faulted:
                ledger.append(seq, ev.t, ev.kind, (ev.u, ev.v),
                              "skipped-duplicate-fault")
                return
            self._link_faulted.add(link_key)
        elif ev.kind == "link-join":
            self._link_faulted.discard(link_key)
        tr.apply_link_event(ev.kind, dev_ids, bandwidth_mbps=ev.bandwidth_mbps,
                            latency_s=ev.latency_s, link=(ev.u, ev.v),
                            loss_rate=ev.loss_rate)
        action = {"link-join": "link-restored",
                  "link-degrade": "link-degraded",
                  "link-loss": "link-lossy"}.get(ev.kind, "link-severed")
        detail = {"devices": dev_ids}
        if ev.bandwidth_mbps is not None:
            detail["bandwidth_mbps"] = ev.bandwidth_mbps
        if ev.loss_rate is not None:
            detail["loss_rate"] = ev.loss_rate
        if ev.kind in ("link-fault", "link-loss"):
            detail["detected"] = True
        ledger.append(seq, ev.t, ev.kind, (ev.u, ev.v), action, detail)

    def _record_decision(self, seq: int, t: float, ledger: EventLedger,
                         ctx: FaultContext, dec) -> None:
        """Mirror of ``SimBackend._record_decision``: silent policies write
        nothing (pre-policy ledgers stay byte-identical), adaptive/forced
        choices become ``recovery-decided`` records whose parity projection
        (``recovery.decision_digest``) matches the simulator's."""
        if not (self.policy.records or dec.forced):
            return
        ledger.append(seq, t, "recovery", ctx.subject, "recovery-decided",
                      decision_detail(ctx, dec))

    def _maybe_reshard(self, seq: int, ev: ChurnEvent, ledger: EventLedger):
        """The trainer side of parallelism-plan resharding: route the
        membership change through the shared recovery policy (the same
        ``evaluate_membership`` SimBackend consults, forced replicate-only
        fall-back included), ledger the decision with the *pure*
        ``moved_bytes`` (identical to SimBackend's), and apply the chosen
        tp on real arrays. There is no virtual clock, so ``reshard-ready``
        lands immediately after ``reshard-started`` (recovery *time* is the
        simulator's job; layout parity is this one's)."""
        coord = self.coordinator_device()
        devices = tuple(sorted(self._members))
        ctx = FaultContext(
            kind="membership-change", t=ev.t,
            subject=(coord.id if coord is not None else -1,),
            n_active=len(devices), min_active=self.min_active,
            state_bytes=self.state_bytes,
            plan=self.plan, reshard_mode=ev.reshard,
            pinned_shape=ev.new_shape, devices=devices,
            tensor_sizes=tuple(self.tensor_sizes))
        dec = self.policy.decide(ctx)
        self._record_decision(seq, ev.t, ledger, ctx, dec)
        if dec.reshard is None:
            if dec.baseline is not None and self.plan is not None:
                self.plan = dec.baseline
            return
        decision = dec.reshard
        cand: ParallelismPlan = decision["plan"]
        tr = self.trainer
        coord = self.coordinator_device()
        subject = coord.id if coord is not None else -1
        ledger.append(seq, ev.t, "reshard", subject, "reshard-started", {
            "old_shape": decision["old_shape"],
            "new_shape": decision["new_shape"],
            "moved_bytes": decision["moved_bytes"],
            "step_s": decision["step_s"],
            "baseline_step_s": decision["baseline_step_s"],
        })
        self.plan = cand
        if cand.tp >= 1 and len(tr.active) % cand.tp == 0:
            sev = tr.apply_reshard(cand.tp, microbatch=cand.microbatch)
            self.results[seq] = sev
        ledger.append(seq, ev.t, "reshard", subject, "reshard-ready", {
            "old_shape": decision["old_shape"],
            "new_shape": decision["new_shape"],
            "moved_bytes": decision["moved_bytes"],
        })

    def _device_for(self, node):
        """Trace node → device: the explicit map from joins/leaves first,
        else the pool device whose id equals the trace node id (the base
        cluster's natural labeling)."""
        if node is None:
            return None
        d = self._node_device.get(node)
        if d is not None:
            return d
        for d in self.trainer.pool:
            if d.id == node:
                return d
        return None

    def drain(self, ledger: EventLedger):
        pass
