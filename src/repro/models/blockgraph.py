"""Dataflow-graph form of a network's block chain.

A bare Python loop over a network's blocks would leave the chain's
buffer structure implicit: each two-pass fused block
(``kernels.convdk_mbconv_fused``) writes a set of
intermediate buffers in pass 1 (the retained DW tensor, the SE pool and
gate scale) that only pass 2 of the SAME block reads — so pass 2 of
block *i* and pass 1 of block *i+1* touch disjoint buffers except for
the activation streamed between them.  That disjointness is exactly what
the cross-block pipelining axis of ``core.autotune`` exploits (pricing a
pipelined boundary as ``max(pass2_us, pass1_us)`` instead of their sum),
and it deserves to be checkable rather than folklore.

``BlockGraph`` makes it explicit: every block becomes a ``BlockNode``
carrying per-pass ``StageIO`` read/write buffer sets plus the block's
apply closure, and ``validate()`` proves each boundary the plan marked
``pipelined`` is hazard-free — the ONLY buffer flowing from the
producer's pass 2 into the consumer's pass 1 is the boundary activation
(which the executor streams strip-by-strip, the one-level-up analogue of
``kernels/staging.py`` double-buffering), with no write-after-write or
write-after-read conflicts on the side buffers.  ``lower(x)`` then
executes the chain in node order, calling each node's closure exactly as
the loop would — forward and grad stay bit-exact because each closure
wraps the whole-block ``custom_vjp`` kernel unchanged.

Buffer naming convention (canonical, used by the builders and tests):

* ``act{i}``    — the activation entering node *i* (node *i* writes
  ``act{i+1}``);
* ``dw{i}``     — node *i*'s retained DW tensor (retain mode only);
* ``pool{i}``   — node *i*'s on-chip SE pool result;
* ``scale{i}``  — node *i*'s SE gate, written by the between-pass SE MLP
  (accounted to pass 1, matching ``perfmodel.mbconv_pass_traffic``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, FrozenSet, Optional, Tuple

import jax

from ..core.perfmodel import DEFAULT_OVERLAP, validate_overlap


class GraphValidationError(ValueError):
    """A BlockGraph chain or overlap annotation is ill-formed."""


@dataclasses.dataclass(frozen=True)
class StageIO:
    """The HBM-level buffer sets one pass of a block touches."""

    reads: FrozenSet[str]
    writes: FrozenSet[str]

    @staticmethod
    def of(reads, writes) -> "StageIO":
        return StageIO(reads=frozenset(reads), writes=frozenset(writes))


@dataclasses.dataclass(frozen=True)
class BlockNode:
    """One block of the chain: per-pass buffer sets + the apply closure.

    ``entry_overlap`` annotates the ENTRY boundary (this node's pass 1
    against the previous node's pass 2) — mirroring
    ``autotune.BlockPlan.entry_overlap``, so a plan lowers 1:1 onto a
    graph.  ``apply`` maps the boundary activation to the next one;
    it is excluded from equality so nodes compare structurally.
    """

    index: int
    name: str
    pass1: StageIO
    pass2: StageIO
    entry_overlap: str = DEFAULT_OVERLAP
    apply: Optional[Callable] = dataclasses.field(
        default=None, compare=False, repr=False)

    def __post_init__(self):
        validate_overlap(self.entry_overlap)

    @property
    def input_buffer(self) -> str:
        return f"act{self.index}"

    @property
    def output_buffer(self) -> str:
        return f"act{self.index + 1}"

    @property
    def one_pass(self) -> bool:
        """True for single-pass families (Fused-MBConv): the whole block
        is pass 1 and pass 2 touches nothing."""
        return not (self.pass2.reads or self.pass2.writes)


def mbconv_stage_io(index: int, mode: str = "retain",
                    residual: bool = False, se: bool = True
                    ) -> Tuple[StageIO, StageIO]:
    """The canonical (pass1, pass2) buffer sets of one two-pass fused
    MBConv block, matching the kernel's dataflow:

    * pass 1 reads the entry activation, writes the SE pool and gate
      scale (the SE MLP between the passes is accounted to pass 1, as in
      ``perfmodel.mbconv_pass_traffic``) plus the retained DW tensor in
      retain mode;
    * pass 2 reads the gate scale plus either the retained DW tensor
      (retain) or the entry activation again (recompute re-runs the
      front end), plus the entry activation for the identity residual
      when present, and writes the exit activation.

    ``se=False`` (a no-SE block, MobileNet-V3's early/middle stages)
    drops the pool and gate-scale buffers from both passes.
    """
    a_in, a_out = f"act{index}", f"act{index + 1}"
    dw, pool, scale = f"dw{index}", f"pool{index}", f"scale{index}"
    if not se:
        # no-SE block: no pool, no gate scale.  retain still stages the
        # DW tensor between the passes; recompute's pass 1 writes NOTHING
        # (the kernel skips it entirely) — the node degenerates toward
        # one-pass, but keeps the two-pass form because the kernel still
        # runs the projection as pass 2.
        p1_writes = {dw} if mode == "retain" else set()
        p2_reads = {dw} if mode == "retain" else {a_in}
        if residual:
            p2_reads = set(p2_reads) | {a_in}
        return (StageIO.of({a_in}, p1_writes),
                StageIO.of(p2_reads, {a_out}))
    p1_writes = {pool, scale}
    p2_reads = {scale}
    if mode == "retain":
        p1_writes.add(dw)
        p2_reads.add(dw)
    else:
        p2_reads.add(a_in)
    if residual:
        p2_reads.add(a_in)
    return (StageIO.of({a_in}, p1_writes),
            StageIO.of(p2_reads, {a_out}))


def fusedmb_stage_io(index: int) -> Tuple[StageIO, StageIO]:
    """The (pass1, pass2) buffer sets of one SINGLE-PASS Fused-MBConv
    block: the whole block is pass 1 (entry activation in, exit
    activation out — the expanded tensor never touches HBM, there is no
    SE side buffer), and pass 2 is EMPTY.  ``validate()`` recognizes the
    empty pass 2 as the one-pass form: the exit activation must then be
    written by pass 1, and a downstream consumer can never pipeline its
    entry against this node (nothing flows producer-pass-2 ->
    consumer-pass-1) — matching ``core.autotune``'s serial pricing of
    boundaries behind a one-pass producer.  The identity residual reads
    the same entry activation pass 1 already reads."""
    a_in, a_out = f"act{index}", f"act{index + 1}"
    return (StageIO.of({a_in}, {a_out}), StageIO.of((), ()))


@dataclasses.dataclass(frozen=True)
class BlockGraph:
    """A validated chain of ``BlockNode``s ``lower()`` executes in order."""

    nodes: Tuple[BlockNode, ...]

    @property
    def pipelined_boundaries(self) -> Tuple[int, ...]:
        """Node indices whose ENTRY boundary is pipelined."""
        return tuple(n.index for n in self.nodes[1:]
                     if n.entry_overlap == "pipelined")

    def validate(self) -> None:
        """Prove the chain well-formed and every pipelined boundary legal.

        Chain (all boundaries): node indices are 0..n-1 in order, each
        node's pass 1 reads its entry activation, and its pass 2 writes
        exactly its exit activation — the RAW chain the executor relies
        on.  Pipelined boundaries additionally require hazard freedom
        between the overlapped stages (producer pass 2 ∥ consumer
        pass 1):

        * the only buffer flowing producer-pass-2 → consumer-pass-1 is
          the boundary activation (streamed strip-by-strip);
        * no write-write conflict between the overlapped stages;
        * consumer pass 1 writes nothing producer pass 2 reads (no WAR
          on the side buffers — e.g. a recompute producer still reading
          ITS entry activation must not see it clobbered).
        """
        for i, node in enumerate(self.nodes):
            if node.index != i:
                raise GraphValidationError(
                    f"node {i} carries index {node.index}; chain order "
                    "and buffer naming must agree")
            if node.input_buffer not in node.pass1.reads:
                raise GraphValidationError(
                    f"{node.name}: pass 1 does not read its entry "
                    f"activation {node.input_buffer!r}")
            writer = node.pass1 if node.one_pass else node.pass2
            if node.output_buffer not in writer.writes:
                raise GraphValidationError(
                    f"{node.name}: "
                    f"{'pass 1' if node.one_pass else 'pass 2'} does not "
                    f"write its exit activation {node.output_buffer!r}")
        if self.nodes and self.nodes[0].entry_overlap == "pipelined":
            raise GraphValidationError(
                f"{self.nodes[0].name}: the first node has no producer "
                "to overlap with")
        for node in self.nodes[1:]:
            if node.entry_overlap != "pipelined":
                continue
            prev = self.nodes[node.index - 1]
            if prev.one_pass:
                raise GraphValidationError(
                    f"boundary {prev.name}->{node.name}: the producer is "
                    "single-pass (no pass 2 to overlap with); the entry "
                    "must be serial")
            streamed = prev.pass2.writes & node.pass1.reads
            if streamed != {node.input_buffer}:
                raise GraphValidationError(
                    f"boundary {prev.name}->{node.name}: pipelining "
                    f"requires exactly the boundary activation "
                    f"{node.input_buffer!r} to flow producer-pass-2 -> "
                    f"consumer-pass-1, got {sorted(streamed)}")
            waw = prev.pass2.writes & node.pass1.writes
            if waw:
                raise GraphValidationError(
                    f"boundary {prev.name}->{node.name}: write-write "
                    f"conflict on {sorted(waw)} between overlapped "
                    "stages")
            war = node.pass1.writes & prev.pass2.reads
            if war:
                raise GraphValidationError(
                    f"boundary {prev.name}->{node.name}: consumer "
                    f"pass 1 overwrites {sorted(war)} while producer "
                    "pass 2 still reads it")

    def lower(self, x):
        """Execute the chain: thread ``x`` through every node's apply
        closure in node order — operation-for-operation identical to the
        sequential loop, so forward and grad are bit-exact with it.  Each
        node runs under ``jax.named_scope(node.name)``, so every op of a
        block (its pads, slices, residual add, SE ops and kernels) carries
        the block's name in its HLO ``op_name``."""
        for node in self.nodes:
            if node.apply is None:
                raise GraphValidationError(
                    f"{node.name}: no apply closure bound; build the "
                    "graph through build_block_graph to lower it")
            with jax.named_scope(node.name):
                x = node.apply(x)
        return x


def build_block_graph(specs, params, *, kcfg=None, mesh=None,
                      plan=None) -> BlockGraph:
    """The ``BlockGraph`` of a block chain (stem and head stay in
    ``mbconv.network_apply``).  Each spec's ``family`` picks the node
    form — two-pass ``mbconv`` nodes (per-pass buffer sets reflecting the
    solved mode and the spec's SE presence) or one-pass ``fusedmb``
    nodes (empty pass 2, categorically serial exits).  Each node's apply
    closure performs the exact block call a sequential loop would make
    — same ``SchedulePin``, same ``in_layout``, the spec's own act/SE
    routing — so ``graph.lower(x)`` is bit-exact with the loop;
    with a ``plan``, each node additionally inherits the plan's solved
    ``entry_overlap``.

    Without a plan every boundary is serial and the buffer sets use the
    nodes' default retain dataflow — the graph is then purely the
    structural form of the loop.
    """
    from ..configs.base import SchedulePin
    from .mbconv import fusedmb_block, mbconv_block

    if plan is not None and len(plan.blocks) != len(specs):
        raise GraphValidationError(
            f"plan covers {len(plan.blocks)} blocks, chain has "
            f"{len(specs)}")
    nodes = []
    for i, sp in enumerate(specs):
        if plan is not None:
            bp = plan.blocks[i]
            # FusedMBSchedule has no mode axis (single pass)
            mode = getattr(bp.schedule, "mode", "retain")
            pin = SchedulePin(mode=getattr(bp.schedule, "mode", None),
                              residency=bp.schedule.residency,
                              collective=bp.schedule.collective)
            overlap = bp.entry_overlap
            in_layout = bp.in_layout
        else:
            mode, overlap = "retain", DEFAULT_OVERLAP
            pin, in_layout = None, "replicated"

        if sp.family == "fusedmb":
            block, kw = fusedmb_block, dict(act=sp.act)
            p1, p2 = fusedmb_stage_io(i)
        else:
            block = mbconv_block
            kw = dict(in_layout=in_layout, exp_act=sp.act, dw_act=sp.act,
                      se_act=sp.se_act, gate_act=sp.gate_act)
            p1, p2 = mbconv_stage_io(i, mode=mode, residual=sp.has_residual,
                                     se=sp.has_se)

        nodes.append(BlockNode(
            index=i, name=f"{sp.family}{i}", pass1=p1, pass2=p2,
            entry_overlap=overlap,
            apply=_block_apply(block, params[f"block{i}"], stride=sp.s,
                               cfg=kcfg, mesh=mesh, pin=pin,
                               overlap=overlap, **kw)))
    return BlockGraph(nodes=tuple(nodes))


def _block_apply(block, params, **kw):
    """``x -> y`` of one block call (its out_layout dropped)."""
    return lambda x: block(x, params, **kw)[0]
