# Copyright 2026. Licensed under the Apache License, Version 2.0.
"""Comm-plan compiler passes: minimum-round packing + cost-modeled choice.

The plan lowering (:mod:`bluefog_tpu.collective.plan`) turns a directed
edge set into rounds of partial permutations, one ``lax.ppermute`` each.
The *naive* decomposition groups edges by ring offset ``(dst - src) %
size``; for circulant topologies (Exp2, ring, fully-connected — every
rank's neighbor set is the same offset set) each group is a FULL
permutation riding ICI and the round count already equals the degree. But
an irregular topology (random digraph, user weight matrix, dynamic-
schedule union) can scatter a handful of edges over O(N) distinct
offsets, and each round is a fixed-latency collective on the gossip hot
path every optimizer step pays.

This module is the pass pipeline that fixes that:

1. **Round packing** (:func:`coloring_perms`): the directed edge set is a
   bipartite multigraph between sources and destinations; packing edges
   into partial permutations (per round: each rank sends ≤ 1 and receives
   ≤ 1) is exactly *edge coloring* that graph. König's theorem says the
   bipartite chromatic index equals the max degree, so the provably
   minimal round count is ``max(max_out_degree, max_in_degree)``
   (:func:`min_rounds`) — achieved constructively with the classic
   Kempe-chain (alternating-path) algorithm. Receiver-side-weight
   semantics survive untouched: each destination still receives from at
   most one source per round, which is all ``weighted_combine`` assumes.
2. **Cost model** (:func:`plan_cost_s`): per round ``alpha +
   bytes / beta`` with the ICI constants shared with
   :mod:`bluefog_tpu.scaling`'s analytic comm accounting. Rounds are
   sequential, so plan cost is ``rounds * round_cost``; the chooser
   (:func:`compile_edges`) takes the coloring only when it strictly
   reduces the round count and keeps the offset grouping on ties — full
   circulant permutations are the ICI fast path and the tie-break
   preserves byte-identical lowering for every regular topology.
3. **Plan-level cache**: compilation is memoized on the canonical edge
   set, so repeated lowerings of the same topology (fresh plan objects,
   window re-lowerings, schedule steps sharing a step graph) dedupe to
   one host-side compile.

This is the plan-synthesis idea of SCCL ("Synthesizing Optimal
Collective Algorithms", arXiv:2008.08708) and Swing's offset-selection
insight applied to the static ``CommPlan`` lowering.

Beyond the latency-optimal point, the compiler carries a **bandwidth
family** (SCCL's latency×bandwidth Pareto front, Swing's short-cutting):

4. **Chunked, pipelined schedules**: a payload split into ``k``
   512-element-aligned chunks (quantized-wire scale groups never
   straddle a split, so chunked output stays bitwise-identical to the
   monolithic lowering) issued in wavefront order — chunk ``c`` of round
   ``r`` alongside chunk ``c+1`` of round ``r-1`` — so a multi-round
   plan's wire time approaches one payload transfer instead of
   ``rounds`` of them. :func:`choose_chunks` is the alpha-beta Pareto
   chooser over ``(rounds, chunks, route)`` per payload size;
   ``BLUEFOG_PLAN_CHUNKS`` is the manual override.
5. **Short-cut routes** (:func:`shortcut_perms`): a multi-hop edge
   (virtual ranks far apart under the serpentine torus placement,
   :mod:`bluefog_tpu.topology.placement`) is decomposed into a relay
   chain of physically-adjacent unit hops spread over consecutive
   rounds, so every round's transfers are congestion-free single ICI
   hops; chunk pipelining then hides the extra relay rounds. Declared
   via ``BLUEFOG_TORUS_DIMS`` (falls back to the virtual ring).
6. **Measured calibration** (:func:`calibrate`): a one-shot probe
   replaces the hardcoded ICI-class ``ROUND_ALPHA_S`` /
   ``ICI_LINK_BYTES_PER_S`` (and measures how much of the ideal chunk
   pipelining this backend actually delivers), so the chooser's
   latency/bandwidth crossover is real on the host it runs on.
"""

import dataclasses
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bluefog_tpu.topology import placement as _placement

__all__ = [
    "ROUND_ALPHA_S",
    "ICI_LINK_BYTES_PER_S",
    "DCN_ROUND_ALPHA_S",
    "DCN_LINK_BYTES_PER_S",
    "LINK_CLASSES",
    "DEFAULT_PAYLOAD_BYTES",
    "CompiledEdges",
    "compile_edges",
    "offset_perms",
    "coloring_perms",
    "shortcut_perms",
    "min_rounds",
    "round_cost_s",
    "plan_cost_s",
    "degraded_round_penalty_s",
    "pipelined_cost_s",
    "predicted_round_costs_s",
    "choose_chunks",
    "chunk_option",
    "CompiledReduceScatter",
    "compile_reduce_scatter",
    "reduce_scatter_chunks",
    "calibrate",
    "calibration",
    "set_calibration",
    "clear_calibration",
    "clear_compile_cache",
]

# Alpha-beta wire model constants (shared with bluefog_tpu.scaling, which
# re-exports them for its analytic cost helpers). Values are the v4/v5e
# ICI class: ~1 us fixed launch + neighbor-hop latency per collective
# round, ~9e10 B/s per-direction link bandwidth. They are the *defaults*
# of the cost model: a one-shot measured probe (:func:`calibrate`)
# replaces them at runtime so the chunk chooser's latency/bandwidth
# crossover reflects the actual host, not the class sheet.
ROUND_ALPHA_S = 1.0e-6
ICI_LINK_BYTES_PER_S = 9.0e10

# DCN class constants — the inter-pod leg of a federated fabric
# (bluefog_tpu.federation). Data-center-network latency is dominated by
# the host round trip (~50 us vs the ~1 us ICI hop) and per-direction
# bandwidth by the pod's WAN share (~25 GB/s aggregate is the v4 pod
# sheet number; a conservative per-link figure). ``pipeline_eff`` 0: the
# DCN leg crosses host NICs whose transfers already overlap, so chunk
# pipelining is priced as a no-win (the chooser keeps 1 chunk).
DCN_ROUND_ALPHA_S = 5.0e-5
DCN_LINK_BYTES_PER_S = 2.5e10

LINK_CLASSES = ("ici", "dcn")

# ResNet50 f32 model payload — the gossip payload used throughout bench's
# evidence set; the default basis for a plan's recorded predicted cost.
DEFAULT_PAYLOAD_BYTES = 25_557_032 * 4

# Chunk splits snap to this element width (the int8-wire scale-group
# width, shared with bluefog_tpu.collective.inner): a chunk boundary off
# the 512 grid would move elements into different quantization scale
# groups and break the bitwise chunked==monolithic guarantee.
CHUNK_ALIGN_ELEMS = 512

# Search cap for the chunk chooser (powers of two up to this).
MAX_CHUNKS = 64


# -- measured calibration ----------------------------------------------------

# Per-link-class calibration store. "ici" is the default class every
# pre-federation caller lands on — an installed single-class pin keeps
# exactly its old meaning. "dcn" is the inter-pod leg's class
# (bluefog_tpu.federation); each class is priced, pinned, and probed
# independently so one fabric's measurement can never leak into the
# other's chunk chooser.
_CAL: Dict[str, Dict[str, float]] = {}

_CLASS_DEFAULTS: Dict[str, Dict[str, object]] = {
    "ici": {
        "alpha_s": ROUND_ALPHA_S,
        "beta_bytes_per_s": ICI_LINK_BYTES_PER_S,
        "pipeline_eff": 1.0,
        "source": "class-constants",
    },
    "dcn": {
        "alpha_s": DCN_ROUND_ALPHA_S,
        "beta_bytes_per_s": DCN_LINK_BYTES_PER_S,
        "pipeline_eff": 0.0,
        "source": "class-constants",
    },
}


def _check_link_class(link_class: str) -> str:
    if link_class not in LINK_CLASSES:
        raise ValueError(
            f"link_class must be one of {LINK_CLASSES}, got {link_class!r}"
        )
    return link_class


def calibration(link_class: str = "ici") -> Dict[str, object]:
    """The active alpha-beta constants for one link class: the measured
    one-shot probe when one has run (or was injected), else the class
    defaults. ``pipeline_eff`` in [0, 1] is the fraction of ideal
    chunk-pipeline overlap the backend delivers (1 under the ICI class
    defaults — the torus-fabric assumption; ~0 on a backend whose
    independent collectives already overlap, where chunking cannot win;
    0 for the DCN class, whose NIC transfers overlap by themselves).
    The returned dict always echoes its ``link_class``."""
    cal = _CAL.get(_check_link_class(link_class))
    out = dict(cal) if cal is not None else dict(_CLASS_DEFAULTS[link_class])
    out["link_class"] = link_class
    return out


def set_calibration(
    alpha_s: float,
    beta_bytes_per_s: float,
    pipeline_eff: float = 1.0,
    source: str = "manual",
    link_class: str = "ici",
) -> None:
    """Install cost-model constants for one link class (tests; or a
    deployment that probes once and pins the result). The default class
    ``"ici"`` preserves the pre-federation single-class behavior —
    existing pins keep pinning exactly what they pinned."""
    _CAL[_check_link_class(link_class)] = {
        "alpha_s": float(alpha_s),
        "beta_bytes_per_s": float(beta_bytes_per_s),
        "pipeline_eff": min(1.0, max(0.0, float(pipeline_eff))),
        "source": source,
    }


def clear_calibration(link_class: Optional[str] = None) -> None:
    """Drop installed calibration for ``link_class``, or every class
    when None (the pre-federation call shape)."""
    if link_class is None:
        _CAL.clear()
    else:
        _CAL.pop(_check_link_class(link_class), None)


def calibrate(
    force: bool = False,
    small_elems: int = 2048,
    large_elems: int = 1 << 21,
    steps: int = 4,
    windows: int = 2,
    link_class: str = "ici",
) -> Dict[str, object]:
    """One-shot measured probe for the cost-model constants.

    Times three tiny jitted programs on the ambient devices (>= 2
    required; single-device hosts keep the class constants):

    - a full-ring ppermute at a small payload -> ``alpha_s`` (per-round
      launch + rendezvous latency);
    - the same at a large payload -> ``beta_bytes_per_s`` from the byte
      delta over the time delta;
    - a 2-round combine at the large payload, monolithic vs 4-chunk
      wavefront -> ``pipeline_eff``: the measured fraction of the ideal
      pipelined speedup this backend delivers. On a fabric where
      independent transfers already overlap (CPU thread pools) the
      measured gain is ~0 and the chooser will correctly never chunk;
      on serialized wires the gain approaches the ideal and the chunk
      crossover lands where the hardware puts it.

    The result is cached process-wide (``force=True`` re-probes) and
    every cost function below prices with it from then on. Invoked
    lazily by the chooser when ``BLUEFOG_PLAN_CALIBRATE=1``;
    tests/test_plan_compiler.py runs it on the suite's virtual mesh.

    ``link_class`` selects which class's constants the probe installs.
    Only ``"ici"`` is probe-able from inside one pod (the ambient
    devices ARE the ICI fabric); ``calibrate(link_class="dcn")`` honors
    an installed per-class pin (``set_calibration(...,
    link_class="dcn")`` — the deployment declares what it measured out
    of band) and otherwise returns the DCN class defaults, because no
    single-host probe can time a cross-pod wire it does not have.
    """
    _check_link_class(link_class)
    if _CAL.get(link_class) is not None and not force:
        # honor ANY installed calibration (a prior probe or a
        # set_calibration() pin) — a deployment that pinned constants
        # must not be silently re-probed by the lazy autocalibrate path
        return calibration(link_class)
    if link_class != "ici":
        return calibration(link_class)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bluefog_tpu.timing import timed_differenced

    devices = jax.devices()
    if len(devices) < 2:
        return calibration()
    n = min(len(devices), 8)
    mesh = Mesh(np.array(devices[:n]), ("cal",))
    fwd = tuple((i, (i + 1) % n) for i in range(n))
    bwd = tuple((i, (i - 1) % n) for i in range(n))

    def timed(body, elems):
        x = jax.device_put(
            np.random.RandomState(0).randn(n, elems).astype(np.float32),
            NamedSharding(mesh, P("cal")),
        )
        fn = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=P("cal"), out_specs=P("cal")
            )
        )
        carry = [x]

        def step():
            carry[0] = fn(carry[0])
            return carry[0][0, 0]

        return timed_differenced(step, steps, windows)[0]

    def one_round(t):
        return lax.ppermute(t, "cal", fwd)

    def two_round(t):
        return lax.ppermute(t, "cal", fwd) + lax.ppermute(t, "cal", bwd)

    def two_round_chunked(t):
        k = 4
        per = max(
            CHUNK_ALIGN_ELEMS,
            (t.size // k) // CHUNK_ALIGN_ELEMS * CHUNK_ALIGN_ELEMS,
        )
        flat = t.reshape(-1)
        parts = [flat[a:a + per] for a in range(0, flat.size, per)]
        ys = [jnp.zeros_like(p) for p in parts]
        rounds = (fwd, bwd)
        for wave in range(len(rounds) + len(parts) - 1):
            for c in range(len(parts)):
                r = wave - c
                if 0 <= r < len(rounds):
                    ys[c] = ys[c] + lax.ppermute(parts[c], "cal", rounds[r])
        return jnp.concatenate(ys).reshape(t.shape)

    t_small = timed(one_round, small_elems)
    t_large = timed(one_round, large_elems)
    alpha = max(t_small, 1e-9)
    dbytes = (large_elems - small_elems) * 4
    beta = dbytes / max(t_large - t_small, 1e-9)

    t_mono = timed(two_round, large_elems)
    t_chunk = timed(two_round_chunked, large_elems)
    # ideal wire ratio for R=2, k=4 at a latency-negligible payload:
    # (R*k)/(R+k-1) = 8/5; measured gain below 1 means chunking HURT.
    ideal_gain = (2 * 4) / (2 + 4 - 1)
    gain = t_mono / max(t_chunk, 1e-9)
    pipeline_eff = min(1.0, max(0.0, (gain - 1.0) / (ideal_gain - 1.0)))

    _CAL["ici"] = {
        "alpha_s": float(alpha),
        "beta_bytes_per_s": float(beta),
        "pipeline_eff": float(pipeline_eff),
        "source": "measured-probe",
        "probe_devices": n,
        "probe_gain_2round_4chunk": float(gain),
    }
    return calibration("ici")


def _maybe_autocalibrate() -> None:
    if os.environ.get("BLUEFOG_PLAN_CALIBRATE", "0") in ("1", "true", "on"):
        try:
            calibrate()
        except Exception:  # devices not up yet: keep class constants
            pass


# -- cost model --------------------------------------------------------------


def round_cost_s(
    payload_bytes: float, congestion: float = 1.0, link_class: str = "ici",
) -> float:
    """Cost of one ppermute round: fixed latency + payload transfer.
    ``congestion`` is the round's max directed-link load under the route
    model (:func:`bluefog_tpu.topology.placement.perm_congestion`) — L
    transfers sharing a link serialize on it. ``link_class`` picks the
    calibrated alpha-beta the round rides ("ici" / "dcn")."""
    cal = calibration(link_class)
    return cal["alpha_s"] + congestion * payload_bytes / cal["beta_bytes_per_s"]


def plan_cost_s(
    n_rounds: int, payload_bytes: float, link_class: str = "ici",
) -> float:
    """Rounds are sequential: plan cost = rounds x per-round cost."""
    return n_rounds * round_cost_s(payload_bytes, link_class=link_class)


def degraded_round_penalty_s(
    payload_bytes: float, factor: float, congestion: float = 1.0
) -> float:
    """Extra seconds one ppermute round pays when a crossing link runs
    at ``factor`` of its healthy bandwidth: the round's modeled cost
    scaled by ``1/factor - 1``. The ONE pricing shared by the chaos
    layer's deterministic wire simulation (the attribution doctor's
    probe delays, :mod:`bluefog_tpu.attribution`) and the autotune
    controller's candidate scorer (:mod:`bluefog_tpu.autotune`) — a
    candidate that still carries a blamed edge must pay exactly the
    slowdown the probes would measure on it."""
    if not 0.0 < factor < 1.0:
        return 0.0
    return (1.0 / factor - 1.0) * round_cost_s(payload_bytes, congestion)


def pipelined_cost_s(
    payload_bytes: float,
    n_chunks: int,
    congestions: Sequence[float],
    link_class: str = "ici",
) -> float:
    """Cost of a chunked wavefront schedule over rounds with the given
    congestion factors.

    ``k = 1`` is the serial plan: ``sum_r (alpha + L_r * B / beta)``.
    For ``k > 1`` the ideal pipeline runs ``R + k - 1`` waves of
    ``B / k``-byte stages (the bottleneck stage repeats ``k - 1``
    times), discounted by the calibrated ``pipeline_eff``: a backend
    that delivers no measured overlap prices chunking at the serial
    cost plus its extra per-chunk launches, so the chooser never picks
    what the fabric cannot deliver.
    """
    cal = calibration(link_class)
    alpha, beta, gamma = (
        cal["alpha_s"], cal["beta_bytes_per_s"], cal["pipeline_eff"]
    )
    k = max(1, int(n_chunks))
    serial = sum(alpha + c * payload_bytes / beta for c in congestions)
    if k == 1:
        return serial
    # a k-chunk schedule issues R*k ppermutes: with zero measured
    # overlap every one of them pays its own launch, i.e. R*(k-1)
    # alphas on top of the serial plan (not just k-1)
    serial_k = serial + len(congestions) * (k - 1) * alpha
    b = payload_bytes / k
    bottleneck = alpha + max(congestions, default=1.0) * b / beta
    ideal = sum(alpha + c * b / beta for c in congestions) + (
        k - 1
    ) * bottleneck
    return gamma * ideal + (1.0 - gamma) * serial_k


Perms = Tuple[Tuple[Tuple[int, int], ...], ...]


@dataclasses.dataclass(frozen=True)
class CompiledEdges:
    """The compiler's output for one edge set: the chosen round structure
    plus the decision record kept on the plan for observability.

    Direct decompositions (offset / coloring) leave ``inject`` and
    ``delivery`` None: every perm pair is an original edge delivered in
    its own round. The short-cut family fills them: ``inject[r]`` lists
    the ranks sending their OWN payload in round ``r`` (all other
    senders forward the transit value they received in round ``r-1``),
    and ``delivery`` maps each original edge to the round whose receive
    completes it — the round where the receiver's combine weight for
    that edge applies.
    """

    perms: Perms
    method: str  # "offset" | "coloring" | "shortcut" — decomposition chosen
    rounds: int
    offset_rounds: int  # the naive (offset-grouped) round count
    lower_bound: int  # König bound: max(max_in_degree, max_out_degree)
    predicted_cost_s: float
    offset_cost_s: float
    route: str = "direct"  # "direct" | "shortcut"
    inject: Optional[Tuple[Tuple[int, ...], ...]] = None
    delivery: Optional[Tuple[Tuple[Tuple[int, int], int], ...]] = None
    congestion: Tuple[float, ...] = ()
    link_class: str = "ici"  # which calibrated alpha-beta priced this plan


def _canonical(edges: Iterable[Tuple[int, int]], size: int) -> Tuple[Tuple[int, int], ...]:
    """Dedupe, drop self loops, validate range, sort — the cache key."""
    out = set()
    for i, j in edges:
        i, j = int(i), int(j)
        if i == j:
            continue
        assert 0 <= i < size and 0 <= j < size, (
            f"edge ({i}, {j}) out of range for size {size}"
        )
        out.add((i, j))
    return tuple(sorted(out))


def offset_perms(edges: Iterable[Tuple[int, int]], size: int) -> Perms:
    """Naive pass: group directed edges by ring offset ``(dst - src) %
    size``. Sources within one offset are distinct, hence destinations
    too, so each group is a partial permutation; circulant topologies
    yield one FULL permutation per offset."""
    by_offset: Dict[int, List[Tuple[int, int]]] = {}
    for i, j in _canonical(edges, size):
        by_offset.setdefault((j - i) % size, []).append((i, j))
    return tuple(
        tuple(sorted(by_offset[offset])) for offset in sorted(by_offset)
    )


def min_rounds(edges: Iterable[Tuple[int, int]], size: int) -> int:
    """König lower bound on the round count: no schedule can beat the
    busiest sender or the busiest receiver."""
    out_deg = [0] * size
    in_deg = [0] * size
    for i, j in _canonical(edges, size):
        out_deg[i] += 1
        in_deg[j] += 1
    return max(max(out_deg, default=0), max(in_deg, default=0))


def coloring_perms(edges: Iterable[Tuple[int, int]], size: int) -> Perms:
    """Minimum-round pass: bipartite edge coloring by Kempe chains.

    Colors the source x destination bipartite graph with exactly
    ``min_rounds`` colors: for each edge ``(u, v)`` pick the smallest
    color ``a`` free at source ``u`` and ``b`` free at destination ``v``;
    if they differ, flip the maximal a/b alternating chain starting at
    ``v`` (it can never reach ``u`` — sources on the chain are entered
    via their a-colored out-edge, and ``a`` is free at ``u``), after
    which ``a`` is free at both ends. O(E * V) worst case, deterministic
    for a sorted edge list.
    """
    edge_list = _canonical(edges, size)
    # color -> peer maps per rank, for each bipartite side
    src_color: List[Dict[int, int]] = [dict() for _ in range(size)]
    dst_color: List[Dict[int, int]] = [dict() for _ in range(size)]

    def first_free(used: Dict[int, int]) -> int:
        c = 0
        while c in used:
            c += 1
        return c

    for u, v in edge_list:
        a = first_free(src_color[u])
        b = first_free(dst_color[v])
        if a != b:
            # Walk the maximal alternating chain from v: the a-colored
            # edge into v, then the b-colored edge out of its source,
            # then a into that edge's destination, ... and swap a<->b
            # along it.
            chain: List[Tuple[int, int, int]] = []  # (src, dst, color)
            cur, want, at_dst = v, a, True
            while True:
                if at_dst:
                    s = dst_color[cur].get(want)
                    if s is None:
                        break
                    chain.append((s, cur, want))
                    cur, at_dst = s, False
                else:
                    d = src_color[cur].get(want)
                    if d is None:
                        break
                    chain.append((cur, d, want))
                    cur, at_dst = d, True
                want = b if want == a else a
            for s, d, c in chain:
                del src_color[s][c]
                del dst_color[d][c]
            for s, d, c in chain:
                nc = b if c == a else a
                src_color[s][nc] = d
                dst_color[d][nc] = s
        src_color[u][a] = v
        dst_color[v][a] = u

    n_colors = 1 + max(
        (c for cols in src_color for c in cols), default=-1
    )
    rounds: List[List[Tuple[int, int]]] = [[] for _ in range(n_colors)]
    for s, cols in enumerate(src_color):
        for c, d in cols.items():
            rounds[c].append((s, d))
    perms = tuple(tuple(sorted(r)) for r in rounds if r)
    _check_rounds(perms, edge_list)
    return perms


def shortcut_perms(
    edges: Iterable[Tuple[int, int]],
    size: int,
    dims: Optional[Sequence[int]] = None,
) -> Tuple[Perms, Tuple[Tuple[int, ...], ...], Tuple[Tuple[Tuple[int, int], int], ...]]:
    """Short-cut (relay) pass: decompose every edge into its unit-hop
    route and schedule the hops over consecutive rounds.

    Swing-style short-cutting for the gossip lowering: an edge between
    virtual ranks that are far apart on the fabric
    (:func:`bluefog_tpu.topology.placement.route_ranks` — the serpentine
    ring by default, dimension-ordered torus moves under declared
    ``BLUEFOG_TORUS_DIMS``) rides its whole route inside ONE ppermute
    round, serializing on every link it crosses. Decomposed into a relay
    chain — round ``r`` moves the value one hop, round ``r+1`` forwards
    it one more — every round's transfers are physically-adjacent single
    hops (congestion 1 by construction: a directed unit link determines
    its sender, and a rank sends at most once per round), and chunk
    pipelining (:func:`pipelined_cost_s`) hides the extra rounds at
    large payloads. The value is moved verbatim (no arithmetic at
    relays), so the receiver-side weight semantics are untouched: the
    delivering round's weight applies exactly as the direct lowering's
    would.

    Scheduling is greedy earliest-start over chains sorted longest
    first (deterministic): a chain occupies consecutive rounds (a relay
    must forward what it received in the previous round — transit does
    not persist), each rank sends <= 1 value per round (its own payload
    when injecting, its transit otherwise) and receives <= 1.

    Returns ``(perms, inject, delivery)``; validated by a host-side
    relay simulation before returning.
    """
    edge_list = _canonical(edges, size)
    chains = []
    for u, v in edge_list:
        ranks = _placement.route_ranks(u, v, size, dims)
        chains.append(((u, v), tuple(zip(ranks[:-1], ranks[1:]))))
    chains.sort(key=lambda c: (-len(c[1]), c[0]))

    rounds: List[List[Tuple[int, int]]] = []
    send_used: List[set] = []
    recv_used: List[set] = []
    inject: List[set] = []
    delivery: List[Tuple[Tuple[int, int], int]] = []

    def fits(start: int, hops) -> bool:
        for t, (a, b) in enumerate(hops):
            r = start + t
            if r < len(rounds) and (a in send_used[r] or b in recv_used[r]):
                return False
        return True

    for (u, v), hops in chains:
        start = 0
        while not fits(start, hops):
            start += 1
        for t, (a, b) in enumerate(hops):
            r = start + t
            while r >= len(rounds):
                rounds.append([])
                send_used.append(set())
                recv_used.append(set())
                inject.append(set())
            rounds[r].append((a, b))
            send_used[r].add(a)
            recv_used[r].add(b)
            if t == 0:
                inject[r].add(a)
        delivery.append(((u, v), start + len(hops) - 1))

    perms = tuple(tuple(sorted(r)) for r in rounds)
    inject_t = tuple(tuple(sorted(i)) for i in inject)
    delivery_t = tuple(sorted(delivery))
    _check_relay(perms, inject_t, delivery_t, edge_list, size)
    return perms, inject_t, delivery_t


def _check_relay(
    perms: Perms,
    inject: Tuple[Tuple[int, ...], ...],
    delivery: Tuple[Tuple[Tuple[int, int], int], ...],
    edge_list: Sequence[Tuple[int, int]],
    size: int,
) -> None:
    """Invariant pass for relay schedules: partial permutation per round,
    and a host-side simulation of the transit recursion proving every
    declared delivery hands the receiver the ORIGINAL source's value —
    the receiver-side-weights contract for short-cut plans."""
    for perm in perms:
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert len(set(srcs)) == len(srcs) and len(set(dsts)) == len(dsts), (
            f"relay round is not a partial permutation: {perm}"
        )
    by_round: Dict[int, Dict[int, int]] = {}
    transit: Dict[int, Optional[int]] = {}  # rank -> origin rank it carries
    for r, perm in enumerate(perms):
        arriving: Dict[int, Optional[int]] = {}
        inj = set(inject[r])
        for a, b in perm:
            arriving[b] = a if a in inj else transit.get(a)
        by_round[r] = {
            b: o for b, o in arriving.items() if o is not None
        }
        transit = arriving
    seen = []
    for (u, v), r in delivery:
        assert by_round.get(r, {}).get(v) == u, (
            f"relay schedule does not deliver {u}->{v} at round {r}"
        )
        seen.append((u, v))
    assert sorted(seen) == list(edge_list), (
        "relay deliveries do not cover the edge set exactly"
    )


def _round_congestions(
    perms: Perms, size: int, route: str = "direct"
) -> Tuple[float, ...]:
    """Per-round max-link-load factors for the cost model. Unit-hop relay
    rounds are 1 by construction; direct rounds are priced by the route
    model only when a physical fabric is declared (congestion on an
    undeclared fabric would be a guess the measured chooser could not
    honor)."""
    if route == "shortcut":
        return (1.0,) * len(perms)
    dims = _placement.declared_torus_dims(size)
    if dims is None:
        return (1.0,) * len(perms)
    return tuple(
        float(_placement.perm_congestion(p, size, dims)) for p in perms
    )


def _check_rounds(perms: Perms, edge_list: Sequence[Tuple[int, int]]) -> None:
    """Invariant pass: every round is a partial permutation (each rank
    sends <= 1 and receives <= 1 — the receiver-side-weights contract)
    and the rounds partition the edge set exactly."""
    seen = []
    for perm in perms:
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert len(set(srcs)) == len(srcs) and len(set(dsts)) == len(dsts), (
            f"round is not a partial permutation: {perm}"
        )
        seen.extend(perm)
    assert sorted(seen) == list(edge_list), (
        "compiled rounds do not partition the edge set"
    )


_COMPILE_CACHE: Dict[Tuple, CompiledEdges] = {}
_COMPILE_CACHE_MAX = 1024


def clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()
    _RS_CACHE.clear()


def compile_edges(
    edges: Iterable[Tuple[int, int]],
    size: int,
    method: str = "auto",
    payload_bytes: Optional[float] = None,
    link_class: str = "ici",
) -> CompiledEdges:
    """Compile a directed edge set into ppermute rounds.

    ``method``: ``"auto"`` (cost-modeled choice between offset grouping
    and the minimal coloring, the default), ``"offset"`` (force the
    naive grouping), ``"coloring"`` (force the minimal coloring) or
    ``"shortcut"`` (the relay/bandwidth family: unit-hop routes, see
    :func:`shortcut_perms`). Memoized on the canonical edge set, so
    repeated lowerings of the same topology dedupe to one compile.

    ``auto`` keeps the structure choice payload-independent (coloring
    only on a strict round-count win, offset on ties — the circulant
    ICI fast path stays byte-identical); the payload-dependent half of
    the Pareto front — how many chunks to pipeline over the chosen
    rounds — is decided at combine-lowering time by
    :func:`choose_chunks`, where the actual wire payload is known.
    """
    if method not in ("auto", "offset", "coloring", "shortcut"):
        raise ValueError(
            "method must be 'auto', 'offset', 'coloring' or 'shortcut', "
            f"got {method!r}"
        )
    from bluefog_tpu import metrics

    _check_link_class(link_class)
    payload = DEFAULT_PAYLOAD_BYTES if payload_bytes is None else payload_bytes
    canon = _canonical(edges, size)
    dims = _placement.declared_torus_dims(size)
    # the default class keeps the pre-federation key shape verbatim (the
    # bitwise flat-fabric pin); a non-default class compiles its own entry
    key = (canon, size, method, payload, dims) if link_class == "ici" \
        else (canon, size, method, payload, dims, link_class)
    hit = _COMPILE_CACHE.get(key)
    if hit is not None:
        metrics.counter("bluefog.plan_cache.hits").inc()
        return hit
    metrics.counter("bluefog.plan_cache.misses").inc()

    naive = offset_perms(canon, size)
    bound = min_rounds(canon, size)
    offset_cost = plan_cost_s(len(naive), payload, link_class=link_class)

    inject = delivery = None
    route = "direct"
    if method == "offset":
        perms, chosen = naive, "offset"
    elif method == "shortcut":
        perms, inject, delivery = shortcut_perms(canon, size, dims)
        chosen, route = "shortcut", "shortcut"
    else:
        colored = naive if len(naive) <= bound else coloring_perms(canon, size)
        assert len(colored) == bound or not canon, (
            f"coloring used {len(colored)} rounds, König bound is {bound}"
        )
        if method == "coloring":
            perms, chosen = colored, "coloring"
        # auto: coloring only on a strict round-count (= cost) win; ties
        # keep the offset grouping whose full circulant perms ride ICI.
        elif len(colored) < len(naive):
            perms, chosen = colored, "coloring"
        else:
            perms, chosen = naive, "offset"

    congestion = _round_congestions(perms, size, route)
    result = CompiledEdges(
        perms=perms,
        method=chosen,
        rounds=len(perms),
        offset_rounds=len(naive),
        lower_bound=bound,
        predicted_cost_s=pipelined_cost_s(
            payload, 1, congestion, link_class=link_class
        ),
        offset_cost_s=offset_cost,
        route=route,
        inject=inject,
        delivery=delivery,
        congestion=congestion,
        link_class=link_class,
    )
    if len(_COMPILE_CACHE) >= _COMPILE_CACHE_MAX:
        _COMPILE_CACHE.pop(next(iter(_COMPILE_CACHE)))
    _COMPILE_CACHE[key] = result
    return result


def predicted_round_costs_s(
    info, payload_bytes: float, n_rounds: Optional[int] = None,
) -> Tuple[float, ...]:
    """Per-round predicted cost at ``payload_bytes`` under the active
    calibration: the model the attribution doctor compares measured
    round times against (per-edge residuals localize degraded links —
    see :mod:`bluefog_tpu.attribution`). ``info`` is a
    :class:`CompiledEdges` (its per-round congestion prices each
    round), or None with an explicit ``n_rounds`` for plans that carry
    no compile record (explicit-weight / dynamic plans): every round is
    then priced congestion-1."""
    if info is not None and info.congestion:
        return tuple(
            round_cost_s(payload_bytes, c) for c in info.congestion
        )
    n = n_rounds if n_rounds is not None else (
        info.rounds if info is not None else 0
    )
    return tuple(round_cost_s(payload_bytes) for _ in range(n))


# -- the (rounds, chunks, route) Pareto chooser ------------------------------


def chunk_option(
    payload_bytes: float,
    congestions: Sequence[float],
    n_elems: Optional[int] = None,
    link_class: str = "ici",
) -> Tuple[int, float]:
    """Best chunk count and its predicted cost for one round structure:
    argmin over powers of two of :func:`pipelined_cost_s`, capped so
    every chunk keeps at least one 512-element scale group (``n_elems``
    when known)."""
    if not congestions:
        return 1, 0.0
    kmax = MAX_CHUNKS
    if n_elems is not None:
        kmax = min(kmax, max(1, int(n_elems) // CHUNK_ALIGN_ELEMS))
    best_k, best_c = 1, pipelined_cost_s(
        payload_bytes, 1, congestions, link_class=link_class
    )
    k = 2
    while k <= kmax:
        c = pipelined_cost_s(
            payload_bytes, k, congestions, link_class=link_class
        )
        if c < best_c:
            best_k, best_c = k, c
        k *= 2
    return best_k, best_c


def choose_chunks(
    compiled,
    payload_bytes: float,
    n_elems: Optional[int] = None,
    method: str = "auto",
    link_class: str = "ici",
) -> int:
    """Per-payload chunk count for a compiled round structure — the
    payload-dependent half of the latency×bandwidth Pareto front.

    ``BLUEFOG_PLAN_CHUNKS`` (int >= 1) is the manual override; under
    forced structure methods (``offset`` / ``coloring`` / ``shortcut``
    without the env override) the chooser stays at 1 so A/B
    measurements isolate one axis at a time. ``compiled`` is a
    :class:`CompiledEdges` (its per-round congestion prices the space)
    or a plain round count. With ``BLUEFOG_PLAN_CALIBRATE=1`` the
    one-shot measured probe runs lazily before the first choice.
    """
    env = os.environ.get("BLUEFOG_PLAN_CHUNKS", "").strip()
    if env:
        try:
            k = int(env)
        except ValueError:
            raise ValueError(
                f"BLUEFOG_PLAN_CHUNKS must be a positive int, got {env!r}"
            )
        if k < 1:
            raise ValueError(
                f"BLUEFOG_PLAN_CHUNKS must be a positive int, got {env!r}"
            )
        if n_elems is not None:
            k = min(k, max(1, int(n_elems) // CHUNK_ALIGN_ELEMS))
        return k
    if method not in (None, "auto"):
        return 1
    _maybe_autocalibrate()
    if isinstance(compiled, CompiledEdges):
        congestions = compiled.congestion or (1.0,) * compiled.rounds
        if link_class == "ici":
            link_class = compiled.link_class
    else:
        congestions = (1.0,) * int(compiled)
    k, _cost = chunk_option(
        payload_bytes, congestions, n_elems, link_class=link_class
    )
    return k


# -- the reduce-scatter family (ZeRO-2 gradient leg) -------------------------


@dataclasses.dataclass(frozen=True)
class CompiledReduceScatter:
    """The compiled round structure of one ring reduce-scatter over a
    ``size`` mesh: ``size - 1`` circulant rounds, round ``t`` shipping
    each sender's slot for the rank ``t`` positions ahead of it. Every
    round is a FULL permutation (the ICI fast path, congestion priced
    by the same route model as the gossip perms), and each rank ships
    exactly one slot per round — ``(size-1) * slot`` bytes total, half
    of a bandwidth-optimal allreduce at the same width."""

    perms: Tuple[Tuple[Tuple[int, int], ...], ...]  # per round: (src, dst)
    size: int
    rounds: int
    congestion: Tuple[float, ...]
    predicted_cost_s: float


_RS_CACHE: Dict[int, CompiledReduceScatter] = {}


def compile_reduce_scatter(
    size: int, payload_bytes: Optional[float] = None,
) -> CompiledReduceScatter:
    """Compile (and memoize) the circulant reduce-scatter structure for
    a ``size`` mesh. The structure depends only on the mesh size — the
    chunk count is chosen per payload by :func:`reduce_scatter_chunks`,
    exactly like :func:`choose_chunks` prices a gossip plan."""
    size = int(size)
    if size < 1:
        raise ValueError(f"reduce-scatter needs a positive mesh, got {size}")
    info = _RS_CACHE.get(size)
    if info is None:
        perms = tuple(
            tuple((r, (r + t) % size) for r in range(size))
            for t in range(1, size)
        )
        congestion = _round_congestions(perms, size, "direct")
        payload = DEFAULT_PAYLOAD_BYTES if payload_bytes is None \
            else float(payload_bytes)
        info = CompiledReduceScatter(
            perms=perms,
            size=size,
            rounds=size - 1,
            congestion=congestion,
            predicted_cost_s=pipelined_cost_s(payload, 1, congestion),
        )
        _RS_CACHE[size] = info
    return info


def reduce_scatter_chunks(
    size: int,
    payload_bytes: float,
    n_elems: Optional[int] = None,
) -> int:
    """Chunk count for a reduce-scatter at ``payload_bytes`` per-round
    slot payload: the calibrated alpha-beta Pareto chooser over the
    circulant round structure, on the same 512-element grain (a chunk
    edge off the grid would split a quantized scale block). ``n_elems``
    is the SLOT width — chunking subdivides the slot each round ships,
    never the slot assignment itself."""
    info = compile_reduce_scatter(size, payload_bytes)
    env = os.environ.get("BLUEFOG_PLAN_CHUNKS", "").strip()
    if env:
        return choose_chunks(info.rounds, payload_bytes, n_elems)
    _maybe_autocalibrate()
    congestions = info.congestion or (1.0,) * info.rounds
    k, _cost = chunk_option(payload_bytes, congestions, n_elems)
    return k
