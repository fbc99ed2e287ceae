"""Vectorized per-prefix best-path election.

The data structure here is the **prefix→advertiser matrix** the ROADMAP's
million-prefix item calls for: one columnar table per PrefixState
revision (cached — metric-only churn never rebuilds it), against which
each rebuild's election is a handful of masked segmented reductions over
the solved root-distance vector instead of a per-prefix Python loop
(DeltaPath's observation that incremental/batched route *derivation* —
not just SPF — is where production-scale wins live).

Two advertiser shapes are vectorized:

  * **plain** — exactly one advertiser, SP_ECMP, no min_nexthop /
    weight constraints: the dominant production shape (every loopback);
    election degenerates to a reachability mask + distance gather, and
    the engines assemble routes per (first-hop set, igp) class.
  * **multi** — 2+ advertisers, ALL of them SP_ECMP with no
    min_nexthop / weight: anycast ECMP. Election is the reference's
    selectBestRoutes semantics as segmented reductions: best metric key
    per prefix (masked argmax), then min IGP among the best advertisers
    (masked argmin over the solved ``d_root``), then the equal-cost
    chosen set for the nexthop union.

Everything else — KSP, UCMP weights, min_nexthop, mixed advertiser
algorithms, LFA in the oracle, installed policy — falls back to the
engines' existing scalar paths (the fallback matrix in docs/Decision.md).
Both engines (the oracle and the TPU backend) consume the same table
and the same NumPy election, so vectorized/scalar and engine/engine
byte-parity hold by shared construction and are gated by tests.

The classification is conservative: a prefix is only vectorized when
its route CANNOT depend on which advertiser wins (all advertisers carry
the plain shape), so the scalar and vectorized outcomes are identical
by case analysis, not by luck.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from openr_tpu.common.constants import DIST_INF
from openr_tpu.types.topology import ForwardingAlgorithm

INF64 = np.int64(DIST_INF)


@dataclass
class MultiTable:
    """Columnar prefix→advertiser matrix for the multi-advertiser
    electable prefixes (CSR layout: slot s belongs to prefix
    ``seg[s]``). Known advertisers come first within each prefix,
    sorted by NAME, so `best_nodes` tuples fall out of a mask without
    a per-prefix sort."""

    prefixes: list  # [M] IpPrefix
    indptr: np.ndarray  # int64 [M+1]
    seg: np.ndarray  # int64 [S] owning prefix row per slot
    adv: np.ndarray  # int64 [S] advertiser node id (0 for unknown)
    # slots are NAME-ordered within each prefix (known first): the
    # winner iterator reads best/chosen rows in slot order to reproduce
    # the scalar path's name-sorted tie-breaks
    known: np.ndarray  # bool  [S] advertiser resolved in this topology
    rank: np.ndarray  # int64 [S] dense metric-key rank (higher = better)
    entries: list  # [S] PrefixEntry per slot
    names: list  # [S] advertiser name per slot


@dataclass
class ComplexTable:
    """Columnar advertisers of `ElectView.complex_items`, the shape
    `MultiTable` has: what a warm start scopes the scalar fallback by.
    Only KNOWN advertisers get a slot: an unknown one cannot become
    reachable without a structural change, which is a full solve."""

    adv: np.ndarray  # int64 [S] advertiser node id
    seg: np.ndarray  # int64 [S] owning index into complex_items
    # items with a KSP2_ED_ECMP entry on ANY advertiser, reachable or
    # not (conservative, as the oracle's warm path is): k-disjoint
    # paths depend on the whole graph, not on advertiser classes
    whole_graph: np.ndarray  # int64 [K] indices into complex_items


@dataclass
class ElectView:
    """One PrefixState revision's election-ready classification."""

    plain_p: list  # [P] IpPrefix (single plain advertiser)
    plain_n: list  # [P] advertiser name
    plain_e: list  # [P] PrefixEntry
    orig: np.ndarray  # int64 [P] advertiser node id
    multi: MultiTable | None
    complex_items: list  # [(prefix, {node: entry})] scalar fallback
    complex_table: ComplexTable  # complex_items' advertisers, columnar
    gen: tuple  # generation token (lineage, rev, structure_key)


@dataclass
class MultiElection:
    """Per-prefix outcome arrays of one multi-table election."""

    survive: np.ndarray  # bool [M] a route exists (reachable, not local)
    local: np.ndarray  # bool [M] my node among the best advertisers
    is_best: np.ndarray  # bool [S] slot in the best-metric-key set
    chosen: np.ndarray  # bool [S] slot in the min-IGP chosen set
    min_igp: np.ndarray  # int64 [M]


def _entry_plain(e) -> bool:
    """Advertiser shape the vectorized election covers: shortest-path
    ECMP with no route-shape constraints."""
    return (
        e.forwarding_algorithm == ForwardingAlgorithm.SP_ECMP
        and not e.min_nexthop
        and not e.weight
    )


def build_elect_view(entries: dict, name_to_id: dict, gen) -> ElectView:
    """Classify a PrefixState's entries into the election view.

    ``entries`` is the prefix → {node: PrefixEntry} map; the walk is
    O(prefixes) and runs once per (prefix revision, topology structure) —
    the result is cached by PrefixState's shared view cell."""
    plain_p: list = []
    plain_n: list = []
    plain_e: list = []
    orig: list = []
    m_prefixes: list = []
    m_counts: list = []
    m_adv: list = []
    m_known: list = []
    m_keys: list = []
    m_entries: list = []
    m_names: list = []
    complex_items: list = []
    c_adv: list = []
    c_seg: list = []
    c_whole: list = []

    def add_complex(prefix, per_node) -> None:
        idx = len(complex_items)
        # copy: the live object mutates per_node dicts in place, and
        # this view may outlive its instance via the shared cell
        complex_items.append((prefix, dict(per_node)))
        known = [name_to_id[n] for n in per_node if n in name_to_id]
        c_adv.extend(known)
        c_seg.extend([idx] * len(known))
        if any(
            e.forwarding_algorithm == ForwardingAlgorithm.KSP2_ED_ECMP
            for e in per_node.values()
        ):
            c_whole.append(idx)

    for prefix, per_node in sorted(entries.items()):
        if len(per_node) == 1:
            (node, entry), = per_node.items()
            nid = name_to_id.get(node)
            if nid is not None and _entry_plain(entry):
                plain_p.append(prefix)
                plain_n.append(node)
                plain_e.append(entry)
                orig.append(nid)
                continue
            # single UNKNOWN advertiser stays scalar (rare, and the
            # scalar path's reachable={} / local handling covers it)
            add_complex(prefix, per_node)
            continue
        if all(_entry_plain(e) for e in per_node.values()):
            # known advertisers first, in NAME order — `best_nodes` /
            # `chosen[0]` tie-breaks are name-sorted in the scalar
            # semantics, and slot order is how the winner iterator
            # reproduces that without a per-prefix sort (node ids need
            # NOT follow name order: synthetic bench CSRs intern
            # numerically); unknown advertisers trail — never eligible,
            # so their order is irrelevant
            known_rows = sorted(
                (n, name_to_id[n]) for n in per_node if n in name_to_id
            )
            unknown_rows = sorted(n for n in per_node if n not in name_to_id)
            m_prefixes.append(prefix)
            m_counts.append(len(per_node))
            for n, nid in known_rows:
                e = per_node[n]
                m_adv.append(nid)
                m_known.append(True)
                m_keys.append(
                    (
                        e.metrics.path_preference,
                        e.metrics.source_preference,
                        -e.metrics.distance,
                    )
                )
                m_entries.append(e)
                m_names.append(n)
            for n in unknown_rows:
                e = per_node[n]
                m_adv.append(0)
                m_known.append(False)
                m_keys.append((0, 0, 0))
                m_entries.append(e)
                m_names.append(n)
            continue
        add_complex(prefix, per_node)

    multi: MultiTable | None = None
    if m_prefixes:
        counts = np.asarray(m_counts, dtype=np.int64)
        indptr = np.concatenate(([0], np.cumsum(counts)))
        keys = np.asarray(m_keys, dtype=np.int64).reshape(-1, 3)
        # dense lexicographic rank: np.unique sorts rows ascending
        # lexicographically, which is exactly metric_key's tuple order
        # (larger = better), so the inverse index IS the rank — exact
        # for arbitrary preference magnitudes, no bit-packing overflow
        _, rank = np.unique(keys, axis=0, return_inverse=True)
        multi = MultiTable(
            prefixes=m_prefixes,
            indptr=indptr,
            seg=np.repeat(np.arange(len(m_prefixes), dtype=np.int64), counts),
            adv=np.asarray(m_adv, dtype=np.int64),
            known=np.asarray(m_known, dtype=bool),
            rank=rank.astype(np.int64).ravel(),
            entries=m_entries,
            names=m_names,
        )
    return ElectView(
        plain_p=plain_p,
        plain_n=plain_n,
        plain_e=plain_e,
        orig=np.asarray(orig, dtype=np.int64),
        multi=multi,
        complex_items=complex_items,
        complex_table=ComplexTable(
            adv=np.asarray(c_adv, dtype=np.int64),
            seg=np.asarray(c_seg, dtype=np.int64),
            whole_graph=np.asarray(c_whole, dtype=np.int64),
        ),
        gen=gen,
    )


def elect_multi_np(
    t: MultiTable, d_vec: np.ndarray, reach_vec: np.ndarray, my_id: int
) -> MultiElection:
    """NumPy election over the multi-advertiser table.

    ``d_vec`` is the solved root-distance vector (int, DIST_INF where
    unreachable) and ``reach_vec`` the per-node reachability mask
    (finite distance AND a surviving first hop); both are indexed by
    node id. Semantics mirror the scalar `_unicast_route` exactly:
    eligibility = reachable-or-self, best = masked argmax over metric-
    key ranks, local = self among best, chosen = masked argmin over
    d_vec within the best set."""
    is_me = t.known & (t.adv == my_id)
    elig = (t.known & reach_vec[t.adv]) | is_me
    r_eff = np.where(elig, t.rank, np.int64(-1))
    best_r = np.maximum.reduceat(r_eff, t.indptr[:-1])
    has = best_r >= 0
    is_best = elig & (r_eff == best_r[t.seg])
    m = len(t.prefixes)
    local = np.zeros(m, dtype=bool)
    np.logical_or.at(local, t.seg[is_best & is_me], True)
    d_adv = np.where(is_best, d_vec[t.adv].astype(np.int64), INF64)
    min_igp = np.minimum.reduceat(d_adv, t.indptr[:-1])
    chosen = is_best & (d_adv == min_igp[t.seg])
    return MultiElection(
        survive=has & ~local,
        local=local,
        is_best=is_best,
        chosen=chosen,
        min_igp=min_igp,
    )


def iter_multi_winners(t: MultiTable, res: MultiElection):
    """Yield per-surviving-prefix route ingredients:
    ``(prefix, best_names, chosen_ids, chosen_names, igp, best_entry)``
    — best_names/chosen_names in name order (slot order), best_entry
    the first chosen slot's PrefixEntry (the scalar path's
    ``reachable[chosen[0]]``)."""
    for i in np.nonzero(res.survive)[0].tolist():
        lo, hi = int(t.indptr[i]), int(t.indptr[i + 1])
        best_rows = [s for s in range(lo, hi) if res.is_best[s]]
        chosen_rows = [s for s in best_rows if res.chosen[s]]
        yield (
            t.prefixes[i],
            tuple(t.names[s] for s in best_rows),
            t.adv[chosen_rows],
            [t.names[s] for s in chosen_rows],
            int(res.min_igp[i]),
            t.entries[chosen_rows[0]],
        )
