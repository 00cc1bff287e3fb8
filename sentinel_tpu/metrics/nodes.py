"""Node registry and per-node statistics state.

The reference keeps a tree of stat-holding objects per dimension:

* ``DefaultNode`` per (resource, context) linked into a call tree
  (reference: slots/nodeselector/NodeSelectorSlot.java:127-186);
* one shared ``ClusterNode`` per resource plus per-origin sub-nodes
  (reference: slots/clusterbuilder/ClusterBuilderSlot.java:49);
* ``EntranceNode`` per context aggregating its children
  (reference: node/EntranceNode.java, context/ContextUtil.java:129-190);
* the global inbound ``Constants.ENTRY_NODE``
  (reference: Constants.java:66).

Every such node here is **one row** of the shared stats tensors
(second window, minute window, thread gauge) — the node "tree" is a
host-side id table plus parent/child lists used only by the
introspection plane; the hot path touches rows, never objects.

Each node kind gets a distinct key prefix in one interner so row ids are
dense across kinds. Capacity caps mirror the reference: 6000 resources
(MAX_SLOT_CHAIN_SIZE), 2000 contexts (MAX_CONTEXT_NAME_SIZE); above the
cap callers receive ``None`` and degrade to pass-through, like
CtSph.lookProcessChain / ContextUtil.trueEnter.
"""

from __future__ import annotations

import threading
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from sentinel_tpu.metrics.events import MetricEvent, NUM_EVENTS
from sentinel_tpu.metrics import metric_array as ma
from sentinel_tpu.models import constants as C


SECOND_CFG = ma.MetricArrayConfig(
    sample_count=C.DEFAULT_SAMPLE_COUNT, interval_ms=C.DEFAULT_WINDOW_INTERVAL_MS
)
MINUTE_CFG = ma.MetricArrayConfig(
    sample_count=C.MINUTE_SAMPLE_COUNT, interval_ms=C.MINUTE_INTERVAL_MS
)


def set_second_window(sample_count: int, interval_ms: int) -> ma.MetricArrayConfig:
    """Rebind the second-window geometry (reference:
    SampleCountProperty.java + IntervalProperty.java — updating either
    rebuilds every StatisticNode's rolling second counter and RESETS its
    statistics; the minute window and thread gauges are untouched).

    This only swaps the module-global config; callers that own stats
    tensors (Engine.retune_second_window) must rebuild them to the new
    geometry. All kernel readers reference ``nodes.SECOND_CFG``
    dynamically and key their jit caches on it, so the next trace bakes
    the new constants."""
    global SECOND_CFG
    sample_count = int(sample_count)
    interval_ms = int(interval_ms)
    if sample_count <= 0 or interval_ms <= 0 or interval_ms % sample_count != 0:
        # SampleCountProperty ignores invalid updates (java:42-49).
        raise ValueError(
            "invalid window geometry: sample_count must divide interval_ms"
        )
    SECOND_CFG = ma.MetricArrayConfig(
        sample_count=sample_count, interval_ms=interval_ms, max_rt=SECOND_CFG.max_rt
    )
    return SECOND_CFG


class StatsState(NamedTuple):
    """Device-resident statistics for all nodes.

    The reference's StatisticNode holds a 1 s rolling window (2×500 ms),
    a 60 s window (60×1 s) and a thread gauge
    (reference: node/StatisticNode.java:90-112), plus — for prioritized
    entries — a future-bucket slab tracking tokens borrowed from
    not-yet-current windows (reference: OccupiableBucketLeapArray +
    FutureBucketLeapArray, slots/statistic/metric/occupy/
    OccupiableBucketLeapArray.java:29-75). ``future_pass[r, b]`` holds
    tokens borrowed for the window starting at ``future_ws[r, b]``;
    while that start is still ahead of now they count as *waiting*.
    Once matured they are swept into the second window by
    :func:`materialize_matured` at the start of every flush (the
    batched form of the reference's bucket-reset copy); between flushes
    the read-side fold (:func:`occupied_in_window`) makes them visible
    to metric reads without mutating state.
    """

    second: ma.MetricArrayState
    minute: ma.MetricArrayState
    threads: jax.Array  # int32 [R]
    future_pass: jax.Array  # int32 [R, B] borrowed tokens per future bucket
    future_ws: jax.Array  # int32 [R, B] aligned start of the borrowed window

    @property
    def n_rows(self) -> int:
        return self.threads.shape[0]


def make_stats(n_rows: int) -> StatsState:
    b = SECOND_CFG.sample_count
    return StatsState(
        second=ma.make_state(n_rows, SECOND_CFG),
        minute=ma.make_state(n_rows, MINUTE_CFG),
        threads=jnp.zeros((n_rows,), dtype=jnp.int32),
        future_pass=jnp.zeros((n_rows, b), dtype=jnp.int32),
        future_ws=jnp.full((n_rows, b), SECOND_CFG.empty_ws, dtype=jnp.int32),
    )


def rebuild_second(state: StatsState) -> StatsState:
    """Rebuild the second window + occupy slab to the CURRENT
    ``SECOND_CFG`` geometry, dropping their contents (the reference's
    ``rollingCounterInSecond = new ArrayMetric(...)`` on a
    SampleCountProperty/IntervalProperty update — a clean statistics
    reset). Minute window and live thread gauges carry over."""
    n = state.n_rows
    b = SECOND_CFG.sample_count
    return state._replace(
        second=ma.make_state(n, SECOND_CFG),
        future_pass=jnp.zeros((n, b), dtype=jnp.int32),
        future_ws=jnp.full((n, b), SECOND_CFG.empty_ws, dtype=jnp.int32),
    )


def grow_stats(state: StatsState, new_rows: int) -> StatsState:
    if new_rows <= state.n_rows:
        return state
    extra = make_stats(new_rows - state.n_rows)
    return StatsState(
        second=ma.grow(state.second, new_rows, SECOND_CFG),
        minute=ma.grow(state.minute, new_rows, MINUTE_CFG),
        threads=jnp.concatenate([state.threads, extra.threads]),
        future_pass=jnp.concatenate([state.future_pass, extra.future_pass]),
        future_ws=jnp.concatenate([state.future_ws, extra.future_ws]),
    )


def occupied_in_window(state: StatsState, now: jax.Array) -> jax.Array:
    """Borrowed tokens whose window is now current (int32 [R]).

    The reference materialises these into the second window when the
    bucket resets (OccupiableBucketLeapArray.newEmptyBucket copies
    borrowArray's matured count); here they are folded in at read time:
    a slab entry counts iff its window has started and is not yet
    deprecated (same strict-age rule as the window arrays).
    """
    age = now - state.future_ws
    current = (age >= 0) & (age <= SECOND_CFG.interval_ms)
    return jnp.sum(jnp.where(current, state.future_pass, 0), axis=1)


def materialize_matured(state: StatsState, now: jax.Array) -> StatsState:
    """Fold matured borrows into the second window and clear their slab
    slots — the batched analog of OccupiableBucketLeapArray.resetWindowTo
    copying borrowArray's bucket into the rolled window (reference:
    OccupiableBucketLeapArray.java:41-55).

    Run once per flush, before admission. The read-side fold
    (:func:`occupied_in_window`) alone is not enough: the slab has only
    ``sample_count`` slots per row, so a *new* borrow whose target
    window reuses a slot would evict matured tokens that no bucket ever
    absorbed, silently refunding them. Materialising first makes slot
    reuse safe. Slab entries land at bucket index
    ``(ws // window_len) % n`` — the same index their window occupies in
    the main array, so the fold is a pure per-(row, bucket) operation.
    """
    ws = state.future_ws  # [R, B]
    age = now - ws
    matured = age >= 0
    live = matured & (age <= SECOND_CFG.interval_ms)
    bws = state.second.window_start
    newer = live & (ws > bws)  # the roll the reference does lazily
    same = live & (ws == bws)  # bucket already current: plain add
    counts = jnp.where(newer[:, :, None], 0, state.second.counts)
    add = jnp.where(same | newer, state.future_pass, 0)
    counts = counts.at[:, :, MetricEvent.PASS].add(add)
    second = state.second._replace(
        counts=counts,
        window_start=jnp.where(newer, ws, bws),
        min_rt=jnp.where(newer, jnp.int32(SECOND_CFG.max_rt), state.second.min_rt),
    )
    return state._replace(
        second=second,
        future_ws=jnp.where(matured, jnp.int32(SECOND_CFG.empty_ws), state.future_ws),
        future_pass=jnp.where(matured, 0, state.future_pass),
    )


def waiting_tokens(state: StatsState, now: jax.Array) -> jax.Array:
    """Tokens borrowed for still-future windows (int32 [R]) —
    ``StatisticNode.waiting()`` (reference: node/StatisticNode.java:337)."""
    future = state.future_ws > now
    return jnp.sum(jnp.where(future, state.future_pass, 0), axis=1)


def _segmented_min(start: jax.Array, v: jax.Array) -> jax.Array:
    """Running minimum of ``v`` that restarts wherever ``start`` is set."""

    def comb(a, b):
        fa, va = a
        fb, vb = b
        return fa | fb, jnp.where(fb, vb, jnp.minimum(va, vb))

    return jax.lax.associative_scan(comb, (start, v))[1]


def apply_run_updates(
    state: StatsState,
    rows: jax.Array,  # int32 [N, 4] node rows per entry, -1 = none
    ts: jax.Array,  # int32 [N]
    valid: jax.Array,  # bool [N]
    run_end: Optional[jax.Array],  # int32 [G] last entry of each run, -1 = padding
    deltas: Dict[int, jax.Array],  # MetricEvent -> int32 [N], second window
    thread_delta: jax.Array,  # int32 [N]
    rt_sample: Optional[jax.Array] = None,  # int32 [N]
    minute_deltas: Optional[Dict[int, jax.Array]] = None,
) -> StatsState:
    """Account a batch on both windows and the thread gauge, one record
    per *run* and node row.

    A run is a contiguous span of entries that share all four node rows
    and one ``ts`` (the host's run table, ``FlushBatch.e_run_end``); its
    entries land in the same (row, bucket) keys, so their deltas can be
    summed before the fan-out to the rows. Each run's sums are two reads
    of the entries' prefix sums (invalid entries count 0), its RT sample
    the minimum over its valid entries; :func:`metric_array.update` then
    merges 4·G records instead of 4·N. Integer sums reassociate exactly
    and a run has one window, so every tensor is bit-identical to one
    record per entry. A run with no valid entry writes nothing (it rolls
    no bucket). ``run_end`` None makes every entry a run of its own.

    ``minute_deltas`` overrides the event deltas for the minute window —
    occupied entries diverge between windows (addOccupiedPass writes
    PASS + OCCUPIED_PASS to the minute counter only, reference:
    node/StatisticNode.java:343-346, while the second window's pass
    materialises when the borrowed window becomes current)."""
    n = ts.shape[0]
    if run_end is None:
        run_end = jnp.arange(n, dtype=jnp.int32)
    minute = {} if minute_deltas is None else minute_deltas
    cols = list(deltas.values()) + list(minute.values()) + [thread_delta, valid]
    # Column-major [C, N]: the entry axis is the long, lane-dense one.
    cs = jnp.cumsum(
        jnp.stack([jnp.where(valid, c, 0).astype(jnp.int32) for c in cols]), axis=1
    )
    live = run_end >= 0
    end = jnp.where(live, run_end, 0)
    prev = jnp.concatenate([jnp.full((1,), -1, jnp.int32), run_end[:-1]])
    sums = cs[:, end] - jnp.where(prev >= 0, cs[:, jnp.maximum(prev, 0)], 0)
    run_ok = live & (sums[-1] > 0)

    g = run_end.shape[0]
    rows_f = rows[end].reshape(-1)  # [4G]
    mask = (rows_f >= 0) & jnp.repeat(run_ok, 4)
    ts_f = jnp.repeat(ts[end], 4)

    def event_matrix(evs, at):
        out = jnp.zeros((g, NUM_EVENTS), dtype=jnp.int32)
        for i, ev in enumerate(evs):
            out = out.at[:, ev].set(sums[at + i])
        return jnp.repeat(out, 4, axis=0)

    d_sec = event_matrix(list(deltas), 0)
    d_min = event_matrix(list(minute), len(deltas)) if minute else d_sec
    rt_f = None
    if rt_sample is not None:
        start = jnp.zeros((n,), dtype=bool).at[jnp.where(live, prev + 1, n)].set(
            True, mode="drop"
        )
        run_min = _segmented_min(start, jnp.where(valid, rt_sample, jnp.int32(2**31 - 1)))
        rt_f = jnp.repeat(run_min[end], 4)

    second = ma.update(SECOND_CFG, state.second, rows_f, ts_f, d_sec, rt_f, mask)
    minute_st = ma.update(MINUTE_CFG, state.minute, rows_f, ts_f, d_min, rt_f, mask)
    thr = jnp.where(mask, jnp.repeat(sums[-2], 4), 0)
    threads = state.threads.at[jnp.where(mask, rows_f, 0)].add(thr, mode="drop")
    return state._replace(second=second, minute=minute_st, threads=threads)


class NodeKind:
    CLUSTER = "C"  # per-resource ClusterNode
    DEFAULT = "D"  # per-(resource, context) DefaultNode
    ORIGIN = "O"  # per-(resource, origin) origin StatisticNode
    ENTRANCE = "E"  # per-context EntranceNode


class NodeRegistry:
    """Host-side name→row table plus the call-tree structure."""

    def __init__(
        self,
        max_resources: int = C.MAX_SLOT_CHAIN_SIZE,
        max_contexts: int = C.MAX_CONTEXT_NAME_SIZE,
    ) -> None:
        self._lock = threading.RLock()
        self._rows: Dict[str, int] = {}
        self._keys: List[str] = []
        self.max_resources = max_resources
        self.max_contexts = max_contexts
        self._n_resources = 0
        self._n_contexts = 0
        # Call tree: entrance row -> child default rows (EntranceNode children).
        self.children: Dict[int, List[int]] = {}
        # Origin rows per cluster row (ClusterNode#originCountMap analog).
        self.origin_rows: Dict[int, Dict[str, int]] = {}
        # The global inbound node is always row 0 (Constants.ENTRY_NODE).
        self.entry_node_row = self._alloc(NodeKind.CLUSTER + ":" + C.TOTAL_IN_RESOURCE_NAME)
        assert self.entry_node_row == 0

    def _alloc(self, key: str) -> int:
        row = self._rows.get(key)
        if row is None:
            row = len(self._keys)
            self._rows[key] = row
            self._keys.append(key)
        return row

    def __len__(self) -> int:
        with self._lock:
            return len(self._keys)

    def key_of(self, row: int) -> str:
        with self._lock:
            return self._keys[row]

    def cluster_row(self, resource: str) -> Optional[int]:
        """Row of the resource's ClusterNode; None above the resource cap."""
        key = NodeKind.CLUSTER + ":" + resource
        with self._lock:
            row = self._rows.get(key)
            if row is not None:
                return row
            if self._n_resources >= self.max_resources:
                return None
            self._n_resources += 1
            return self._alloc(key)

    def default_row(self, resource: str, context: str) -> Optional[int]:
        """Row of the per-context DefaultNode (NodeSelectorSlot.java:135-180)."""
        key = NodeKind.DEFAULT + ":" + resource + "|" + context
        with self._lock:
            row = self._rows.get(key)
            if row is not None:
                return row
            row = self._alloc(key)
            ent = self.entrance_row(context)
            if ent is not None:
                self.children.setdefault(ent, []).append(row)
            return row

    def origin_row(self, resource: str, origin: str) -> Optional[int]:
        """Row of the per-origin node under the resource's ClusterNode
        (ClusterBuilderSlot.java:49+, ClusterNode#getOrCreateOriginNode)."""
        if not origin:
            return None
        crow = self.cluster_row(resource)
        if crow is None:
            return None
        key = NodeKind.ORIGIN + ":" + resource + "|" + origin
        with self._lock:
            row = self._rows.get(key)
            if row is None:
                row = self._alloc(key)
                self.origin_rows.setdefault(crow, {})[origin] = row
            return row

    def entrance_row(self, context: str) -> Optional[int]:
        """Row of the context's EntranceNode; None above the 2000 cap."""
        key = NodeKind.ENTRANCE + ":" + context
        with self._lock:
            row = self._rows.get(key)
            if row is not None:
                return row
            if self._n_contexts >= self.max_contexts:
                return None
            self._n_contexts += 1
            return self._alloc(key)

    def promote_cluster_row(self, resource: str) -> int:
        """Cluster-row allocation that IGNORES the resource cap — the
        sketch tier's promotion grant (runtime/sketch.py): an over-cap
        resource that proved itself a heavy hitter deserves the dense
        row the first-come-first-served cap refused it. Rows are never
        released, so the TIER budgets cumulative grants (8x its
        ``promote.max`` — see ``SketchTier._cap_grants``); a churn of
        distinct over-cap heavy hitters cannot regrow unbounded
        per-key state through this door."""
        key = NodeKind.CLUSTER + ":" + resource
        with self._lock:
            row = self._rows.get(key)
            if row is not None:
                return row
            self._n_resources += 1
            return self._alloc(key)

    def lookup_cluster_row(self, resource: str) -> Optional[int]:
        with self._lock:
            return self._rows.get(NodeKind.CLUSTER + ":" + resource)

    def resources(self) -> List[Tuple[str, int]]:
        """All (resource, cluster_row) pairs (ClusterBuilderSlot map view)."""
        prefix = NodeKind.CLUSTER + ":"
        with self._lock:
            return [
                (k[len(prefix):], r)
                for k, r in self._rows.items()
                if k.startswith(prefix) and r != self.entry_node_row
            ]

    def keys_snapshot(self) -> List[str]:
        """Row-ordered key list (what a durable checkpoint carries so a
        restarted process can rebuild the name→row mapping)."""
        with self._lock:
            return list(self._keys)

    def adopt_keys(self, keys: List[str]) -> Dict[int, int]:
        """Replay another registry's row-ordered key list through the
        PUBLIC registration paths (caps + call-tree structure apply
        exactly as live registration would) and return the old-row →
        new-row mapping for every key that got a row — the durable
        restore's stats remap (runtime/failover.restore_durable). On a
        FRESH registry the mapping is the identity; on a registry that
        already served traffic, rows land wherever the live order put
        them. Keys refused by the caps are simply absent from the map
        (their window rows cold-start, same as any over-cap node)."""
        out: Dict[int, int] = {}
        for old_row, key in enumerate(keys):
            kind, _, rest = key.partition(":")
            row: Optional[int] = None
            if kind == NodeKind.CLUSTER:
                row = self.cluster_row(rest)
            elif kind == NodeKind.ENTRANCE:
                row = self.entrance_row(rest)
            elif kind == NodeKind.DEFAULT:
                res, _, ctx = rest.partition("|")
                row = self.default_row(res, ctx)
            elif kind == NodeKind.ORIGIN:
                res, _, org = rest.partition("|")
                row = self.origin_row(res, org)
            if row is not None:
                out[old_row] = row
        return out

    def entrance_children(self, context: str) -> List[int]:
        with self._lock:
            row = self._rows.get(NodeKind.ENTRANCE + ":" + context)
            if row is None:
                return []
            return list(self.children.get(row, ()))

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self._keys.clear()
            self.children.clear()
            self.origin_rows.clear()
            self._n_resources = 0
            self._n_contexts = 0
            self.entry_node_row = self._alloc(NodeKind.CLUSTER + ":" + C.TOTAL_IN_RESOURCE_NAME)
