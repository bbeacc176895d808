"""Distributed LSH dedup step (shard_map; the production-mesh path).

Maps the paper's database designs onto a TPU pod (DESIGN.md §2):

* Docs are sharded over every mesh device ("docs" view of the mesh) —
  each device holds a *band_part* (its doc slice × all bands), i.e. the
  paper's Cassandra **Design 2** layout.
* Candidate generation per band is a bucket-by-value ``all_to_all``
  (value-range partitioning — the "select * where band_id = id" query
  becomes an ICI shuffle) followed by a local lexicographic sort and run
  detection — the paper's sort-based method (§3.6 method 2).
* Star edges (member -> run head) go through a **two-stage verify**:

  1. *On-device prefix prescreen* (inside the all_to_all): each run
     member is compared to its run head over the exchanged
     ``verify_k``-signature prefix; edges whose prefix estimate clears
     ``edge_threshold - prescreen_margin`` survive into bounded,
     statically-shaped per-device edge buffers.  The margin keeps the
     prescreen high-recall: a k-row prefix is a noisy estimate of the
     full M-row agreement, so the final thresholding is NOT done here.
  2. *Batched full-signature verify on the merge*: either on the host
     (``stage2="host"``: ``cluster_step_output`` drives the surviving
     edges through the shared staged engine —
     ``candidates.ShardedEdgeSource`` -> ``verify.ShardedEdgeVerifier``
     (numpy / jnp / ``kernels.sigjaccard`` backends) ->
     ``engine.cluster_source`` -> ``ThresholdUnionFind``) or resident
     on the accelerator (``stage2="device"``: the
     ``kernels.sigjaccard.masked_indexed_pair_counts`` fused gather +
     full-M kernel runs under the same shard_map over each device's own
     signature shard; cross-shard edges are scored there too by
     exchanging a bounded per-device buffer of straggler signature rows
     inside the same collective round — see ``sig_row_capacity`` — so
     edges arrive at the merge already fully scored and
     ``verify.DeviceScoredEdgeVerifier`` is a pass-through whose host
     re-score path handles only row-buffer *overflow*).
     Thresholds, estimator semantics, exclusion stats, and union-find
     semantics are identical to the host and streaming paths either way.

**Band-group streaming** (DESIGN.md §3): the step's b bands are split
into ``band_groups`` groups of b/G bands, each emitting its *own*
bounded per-device edge buffer + overflow counter instead of one
end-of-step gather.  ``make_streamed_dedup_step`` dispatches every
group's shuffle asynchronously and ``cluster_step_output`` consumes the
buffers as a stream (``engine.ClusterAccumulator``): the host merge of
group g materializes only group g's buffer, so it overlaps the device
shuffle of groups g+1..G-1.

Everything is static-shape: buckets and edge buffers have fixed capacity
with overflow *counted* (never silently dropped) — when any device
overflowed, ``cluster_step_output`` falls back through the SAME engine
over a host ``BandMatrixSource`` built from the step's own signatures,
accumulating into the same union-find, so no candidate is ever lost.

Global doc ids come from a per-device ``doc_offsets`` input (default:
the contiguous row offsets), so chunked or ragged corpora can assign
collision-free ids across multiple step invocations.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.hashing import U32_MAX
from repro.core.lsh import band_values
from repro.core.minhash import signatures
from repro.core.shingle import ngram_hashes

INVALID = jnp.uint32(U32_MAX)

STAGE2_MODES = ("host", "device")


@dataclass(frozen=True)
class DistLSHConfig:
    ngram: int = 8
    num_hashes: int = 100
    rows_per_band: int = 2
    verify_k: int = 32          # signature prefix length exchanged for verify
    edge_threshold: float = 0.75
    prescreen_margin: float = 0.15  # stage-1 keeps est >= edge_t - margin
    bucket_slack: float = 2.0   # capacity = slack * D_local / n_dev
    edge_capacity: int = 4096   # prescreened-edge buffer per device/group
    m_chunk: int = 16
    band_groups: int = 1        # G bounded buffers of b/G bands each
    stage2: str = "host"        # full-signature verify: "host" | "device"
    sig_row_capacity: int = 1024  # cross-shard published-row buffer (0: off)
    fused_ingest: bool = False  # one-pass Pallas shingle->minhash->fold
    byte_ingest: bool = False   # step inputs are uint8 bytes, not tokens

    @property
    def num_bands(self) -> int:
        return self.num_hashes // self.rows_per_band

    @property
    def prescreen_threshold(self) -> float:
        """Stage-1 on-device prefix-prescreen keep threshold."""
        return max(0.0, self.edge_threshold - self.prescreen_margin)

    @property
    def bands_per_group(self) -> int:
        if self.num_bands % self.band_groups != 0:
            raise ValueError(
                f"band_groups={self.band_groups} does not divide "
                f"num_bands={self.num_bands}")
        return self.num_bands // self.band_groups


def docs_mesh(devices=None) -> Mesh:
    """Flat 'docs' view over all devices (same devices as the prod mesh)."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    return Mesh(devices.reshape(-1), ("docs",))


def _bucket_scatter(entries: jnp.ndarray, bucket: jnp.ndarray,
                    n_dev: int, cap: int):
    """Scatter entries (D_loc, F) into (n_dev, cap, F) by bucket id.

    Returns (out, overflow_count).  Overflow entries are dropped from the
    buffer but counted.
    """
    d_loc, f = entries.shape
    order = jnp.argsort(bucket)              # stable
    sb = bucket[order]
    se = entries[order]
    idx = jnp.arange(d_loc, dtype=jnp.int32)
    heads = jnp.concatenate([jnp.array([True]), sb[1:] != sb[:-1]])
    seg_start = jax.lax.cummax(jnp.where(heads, idx, 0), axis=0)
    pos = idx - seg_start
    ok = pos < cap
    overflow = jnp.sum(~ok)
    out = jnp.full((n_dev * cap, f), INVALID, dtype=jnp.uint32)
    flat_idx = jnp.where(ok, sb * cap + pos, n_dev * cap)  # OOB drop
    out = out.at[flat_idx].set(se, mode="drop")
    return out.reshape(n_dev, cap, f), overflow


def _band_exchange_and_edges(band_hi, band_lo, doc_ids, sig_k, cfg,
                             axis_name: str, n_dev: int, cap: int):
    """One band: bucket -> all_to_all -> sort -> star edges -> prescreen.

    All inputs are per-device locals:
      band_hi/lo: (D_loc,) uint32; doc_ids: (D_loc,) uint32 global ids;
      sig_k: (D_loc, k) uint32.
    Returns (edges (n_dev*cap, 2) uint32, prefix ests (n_dev*cap,) f32,
             edge_mask, n_candidates, overflow).  ``edge_mask`` marks
    stage-1 survivors (prefix estimate >= prescreen threshold); the
    final ``edge_threshold`` decision happens in stage 2 with full
    signatures (device-resident or on the host merge).
    """
    k = cfg.verify_k
    shift = 32 - max(1, int(np.log2(n_dev))) if n_dev > 1 else 32
    bucket = (band_hi >> shift).astype(jnp.int32) if n_dev > 1 else (
        jnp.zeros_like(band_hi, dtype=jnp.int32))
    entries = jnp.concatenate(
        [band_hi[:, None], band_lo[:, None], doc_ids[:, None], sig_k],
        axis=-1,
    ).astype(jnp.uint32)                      # (D_loc, 3 + k)
    boxed, overflow = _bucket_scatter(entries, bucket, n_dev, cap)
    if n_dev > 1:
        boxed = jax.lax.all_to_all(boxed, axis_name, 0, 0, tiled=False)
    recv = boxed.reshape(n_dev * cap, 3 + k)

    hi, lo, doc = recv[:, 0], recv[:, 1], recv[:, 2]
    sig = recv[:, 3:]
    valid = doc != INVALID
    # Sort invalids to the end: key (valid desc, hi, lo).
    inv_key = (~valid).astype(jnp.uint32)
    iota = jnp.arange(hi.shape[0], dtype=jnp.uint32)
    inv_s, hi_s, lo_s, doc_s, perm = jax.lax.sort(
        (inv_key, hi, lo, doc, iota), num_keys=3)
    sig_s = sig[perm]
    valid_s = inv_s == 0

    same = (hi_s[1:] == hi_s[:-1]) & (lo_s[1:] == lo_s[:-1]) & valid_s[1:]
    heads = jnp.concatenate([jnp.array([True]), ~same])
    idx = jnp.arange(hi_s.shape[0], dtype=jnp.int32)
    head_idx = jax.lax.cummax(jnp.where(heads, idx, 0), axis=0)
    head_doc = doc_s[head_idx]
    head_sig = sig_s[head_idx]
    cand_mask = (~heads) & valid_s            # member of a run
    est = jnp.mean((sig_s == head_sig).astype(jnp.float32), axis=-1)
    edge_mask = cand_mask & (est >= cfg.prescreen_threshold)
    edges = jnp.stack([head_doc, doc_s], axis=-1)
    return edges, est, edge_mask, jnp.sum(cand_mask), overflow


def _prescreen_scan(bands_g, doc_ids, sig_k, cfg, axis: str,
                    n_dev: int, cap: int):
    """Scan one band-group's bands into a bounded per-device edge buffer.

    bands_g: (D_loc, bg, 2) local band slice.  Returns
    (buf (e_cap, 2), buf_sim (e_cap,), emask (e_cap,), stats (1, 3))
    where stats rows are [edge_count, candidates, overflow].
    """
    e_cap = cfg.edge_capacity
    bg = bands_g.shape[1]

    def per_band(carry, j):
        buf, buf_sim, count, tot_cand, tot_ovf = carry
        edges, est, emask, n_cand, ovf = _band_exchange_and_edges(
            bands_g[:, j, 0], bands_g[:, j, 1], doc_ids, sig_k,
            cfg, axis, n_dev, cap)
        # Append masked edges into the fixed buffer.
        offs = jnp.cumsum(emask.astype(jnp.int32)) - 1
        dst = jnp.where(emask, count + offs, e_cap)  # OOB drop
        buf = buf.at[dst].set(edges, mode="drop")
        buf_sim = buf_sim.at[dst].set(est, mode="drop")
        new_count = jnp.minimum(count + jnp.sum(emask), e_cap)
        dropped = count + jnp.sum(emask) - new_count
        return (buf, buf_sim, new_count, tot_cand + n_cand,
                tot_ovf + ovf + dropped), None

    buf0 = jnp.full((e_cap, 2), INVALID, dtype=jnp.uint32)
    sim0 = jnp.zeros((e_cap,), dtype=jnp.float32)
    (buf, buf_sim, count, n_cand, ovf), _ = jax.lax.scan(
        per_band, (buf0, sim0, jnp.int32(0), jnp.int32(0), jnp.int32(0)),
        jnp.arange(bg))
    emask = jnp.arange(e_cap) < count
    stats = jnp.stack([count, n_cand, ovf]).astype(jnp.int32)[None]
    return buf, buf_sim, emask, stats


def make_streamed_dedup_step(cfg: DistLSHConfig, mesh: Mesh, *,
                             stage2: str | None = None):
    """Build the band-group streamed sharded dedup step for ``mesh``.

    Signature: (tokens (D, L) uint32, lengths (D,) int32, seeds (M,),
                doc_offsets (n_dev,) uint32 | None)
      -> dict(sig (D, M), stage2,
              groups=[dict(edges (n_dev*E_cap, 2), prescreen_sims,
                           edge_mask, stats (n_dev, 3), band_start,
                           [device_sims, device_covered]), ...])

    Every group's shuffle is dispatched before the function returns
    (JAX async dispatch): converting group g's buffers to numpy blocks
    on group g alone, which is how ``cluster_step_output`` overlaps the
    host merge of group g with the device shuffle of group g+1.

    With ``stage2="device"`` each group additionally carries
    ``device_match_counts``/``device_covered``/``row_overflow``: full-M
    agreement counts computed on the accelerator by the
    ``kernels.sigjaccard`` fused kernels under shard_map — each device
    scores the gathered group edges whose two endpoints fall in its own
    signature shard, cross-shard edges are scored by the head
    endpoint's owner against the member row exchanged through a bounded
    per-device row buffer (``cfg.sig_row_capacity``; overflow counted),
    and a psum combines the disjoint contributions.  Only edges whose
    member row overflowed the exchange buffer stay uncovered and fall
    back to the host re-score path
    (``verify.DeviceScoredEdgeVerifier`` stragglers).

    ``doc_offsets[i]`` is the global doc id of device i's first row;
    it defaults to the contiguous row offsets ``i * D_loc``.  Callers
    that process a ragged corpus in several chunks MUST pass offsets so
    ids from different invocations cannot collide (the historical
    ``dev * d_loc + arange(d_loc)`` assignment restarted at 0 for every
    chunk and silently aliased distinct documents in the merged edges).
    """
    stage2 = cfg.stage2 if stage2 is None else stage2
    if stage2 not in STAGE2_MODES:
        raise ValueError(f"unknown stage2 mode {stage2!r}")
    n_dev = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    axis = mesh.axis_names[0]
    G = cfg.band_groups
    bg = cfg.bands_per_group

    def local_prepare(tokens, lengths, seeds):
        if cfg.byte_ingest:
            # Zero-copy shard prepare: ``tokens`` is a (D_loc, LB) uint8
            # byte matrix (see ``shingle.pack_bytes``) and the whole
            # tokenize -> shingle -> minhash -> fold chain runs in one
            # device-resident pass feeding the all_to_all directly.
            # Shapes are pow2-bucketed at the session dispatch layer
            # (pack_bytes width), the same contract as the fused branch.
            from repro.kernels.byte_shingle import bytes_to_bands

            # repro-lint: disable=RPR003 — widths bucketed by callers
            sig, bands, _ = bytes_to_bands(
                tokens, lengths, seeds, n=cfg.ngram,
                r=cfg.rows_per_band)
            return sig, bands
        if cfg.fused_ingest:
            # One device-resident Pallas pass per shard: n-gram hashes
            # and the minhash cube never leave VMEM, and the all_to_all
            # below is fed directly — signatures never round-trip
            # through the host.  Bit-identical to the staged branch.
            from repro.kernels.fused_ingest import fused_ingest

            sig, bands, _ = fused_ingest(
                tokens, lengths, seeds, n=cfg.ngram,
                r=cfg.rows_per_band)
            return sig, bands
        ng, valid = ngram_hashes(tokens, lengths, n=cfg.ngram)
        sig = signatures(ng, valid, seeds, m_chunk=cfg.m_chunk)
        bands = band_values(sig, cfg.rows_per_band)  # (D_loc, b, 2)
        return sig, bands

    prepare = jax.jit(shard_map(
        local_prepare,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P()),
        out_specs=(P(axis), P(axis)),
        check_vma=False,
    ))

    def local_group(bands_g, sig, doc_offset):
        # bands_g: (D_loc, bg, 2); sig: (D_loc, M); doc_offset: (1,).
        d_loc = sig.shape[0]
        cap = max(1, int(np.ceil(cfg.bucket_slack * d_loc / n_dev)))
        doc_ids = doc_offset[0].astype(jnp.uint32) + jnp.arange(
            d_loc, dtype=jnp.uint32)
        sig_k = sig[:, : cfg.verify_k]
        buf, buf_sim, emask, stats = _prescreen_scan(
            bands_g, doc_ids, sig_k, cfg, axis, n_dev, cap)
        if stage2 != "device":
            return buf, buf_sim, emask, stats
        # Device-resident stage 2: gather the group's edge buffers from
        # every device, score the edges whose two endpoints live in THIS
        # device's signature shard with the fused full-M kernel, and
        # psum the disjoint masked contributions into a replicated
        # (n_dev * e_cap,) vector (ordering matches the P(axis) gather
        # of the buffers themselves).  The kernel emits exact agreement
        # *counts*; the /M division happens on the host merge in numpy
        # so the scores are bit-identical to the host estimator.
        from repro.kernels import sigjaccard

        all_edges = jax.lax.all_gather(buf, axis, axis=0, tiled=False)
        all_emask = jax.lax.all_gather(emask, axis, axis=0, tiled=False)
        # int32 wraparound arithmetic is exact mod 2^32, so the shard
        # range test below is correct over the full uint32 id space
        # (INVALID slots are masked out via the edge mask).
        flat = all_edges.reshape(-1, 2).astype(jnp.int32)
        off = doc_offset[0].astype(jnp.int32)
        a_loc = flat[:, 0] - off
        b_loc = flat[:, 1] - off
        mask_flat = all_emask.reshape(-1)
        a_in = (a_loc >= 0) & (a_loc < d_loc)
        b_in = (b_loc >= 0) & (b_loc < d_loc)
        local = mask_flat & a_in & b_in
        counts = sigjaccard.masked_indexed_pair_counts(
            sig, a_loc, b_loc, local)
        covered = local
        row_ovf = jnp.zeros((1,), dtype=jnp.int32)
        rc = cfg.sig_row_capacity
        if n_dev > 1 and rc > 0:
            # Cross-shard straggler scoring: exchange a BOUNDED buffer
            # of signature rows inside the same collective round so
            # cross-shard edges are scored on-accelerator too.  An edge
            # (head, member) with endpoints on different shards is
            # scored by the HEAD's owner, which needs the member's row:
            # each device publishes the (deduplicated) member rows it
            # owns for head-remote edges, capacity ``sig_row_capacity``
            # with overflow counted — overflowed rows simply leave those
            # edges uncovered, and the host merge re-scores exactly that
            # overflow remainder (``DeviceScoredEdgeVerifier``).
            publish = mask_flat & b_in & (~a_in)
            need = jnp.where(publish, b_loc, d_loc)
            s = jnp.sort(need)
            uniq = jnp.concatenate(
                [jnp.array([True]), s[1:] != s[:-1]]) & (s < d_loc)
            pos = jnp.cumsum(uniq.astype(jnp.int32)) - 1
            n_pub = jnp.sum(uniq)
            dst = jnp.where(uniq & (pos < rc), pos, rc)  # OOB drop
            row_ids = jnp.full((rc,), INVALID, dtype=jnp.uint32)
            rows = jnp.zeros((rc, sig.shape[1]), dtype=jnp.uint32)
            glob = doc_offset[0].astype(jnp.uint32) + s.astype(jnp.uint32)
            row_ids = row_ids.at[dst].set(glob, mode="drop")
            rows = rows.at[dst].set(
                sig[jnp.clip(s, 0, d_loc - 1)].astype(jnp.uint32),
                mode="drop")
            row_ovf = jnp.maximum(n_pub - rc, 0).astype(jnp.int32)[None]
            tbl_ids = jax.lax.all_gather(
                row_ids, axis, axis=0, tiled=False).reshape(-1)
            tbl_rows = jax.lax.all_gather(
                rows, axis, axis=0, tiled=False).reshape(-1, sig.shape[1])
            # Score the cross edges whose head lives in my shard: look
            # the member row up in the exchanged table by global id
            # (published ids are unique — one owner, deduplicated).
            score_mine = mask_flat & a_in & (~b_in)
            order = jnp.argsort(tbl_ids)
            sorted_ids = tbl_ids[order]
            member_glob = all_edges.reshape(-1, 2)[:, 1]
            pos_b = jnp.clip(jnp.searchsorted(sorted_ids, member_glob),
                             0, sorted_ids.shape[0] - 1)
            hit = (sorted_ids[pos_b] == member_glob) & score_mine
            a_rows = sig[jnp.clip(a_loc, 0, d_loc - 1)]
            b_rows = tbl_rows[order[pos_b]]
            counts = counts + sigjaccard.masked_pair_counts(
                a_rows, b_rows, hit)
            covered = covered | hit
        dev_counts = jax.lax.psum(counts, axis)
        dev_cov = jax.lax.psum(covered.astype(jnp.int32), axis) > 0
        return buf, buf_sim, emask, stats, dev_counts, dev_cov, row_ovf

    group_out_specs = (P(axis), P(axis), P(axis), P(axis))
    if stage2 == "device":
        group_out_specs = group_out_specs + (P(), P(), P(axis))
    group_step = jax.jit(shard_map(
        local_group,
        mesh=mesh,
        in_specs=(P(axis), P(axis), P(axis)),
        out_specs=group_out_specs,
        check_vma=False,
    ))

    def step(tokens, lengths, seeds, doc_offsets=None):
        tokens = jnp.asarray(tokens)
        if doc_offsets is None:
            d_loc = tokens.shape[0] // n_dev
            doc_offsets = jnp.uint32(d_loc) * jnp.arange(
                n_dev, dtype=jnp.uint32)
        doc_offsets = jnp.asarray(doc_offsets).astype(jnp.uint32)
        sig, bands = prepare(tokens, jnp.asarray(lengths),
                             jnp.asarray(seeds))
        groups = []
        for g in range(G):
            bands_g = jax.lax.slice_in_dim(bands, g * bg, (g + 1) * bg,
                                           axis=1)
            outs = group_step(bands_g, sig, doc_offsets)
            gout = {
                "edges": outs[0], "prescreen_sims": outs[1],
                "edge_mask": outs[2], "stats": outs[3],
                "band_start": g * bg,
            }
            if stage2 == "device":
                gout["device_match_counts"] = outs[4]
                gout["device_covered"] = outs[5]
                gout["row_overflow"] = outs[6]
            groups.append(gout)
        return {"sig": sig, "groups": groups, "stage2": stage2}

    return step


def make_dedup_step(cfg: DistLSHConfig, mesh: Mesh):
    """Build the jit-able sharded dedup step for ``mesh`` ('docs' axis).

    Signature: (tokens (D, L) uint32, lengths (D,) int32, seeds (M,),
                doc_offsets (n_dev,) uint32 | None)
      -> dict(edges (G*n_dev*E_cap, 2), prescreen_sims, edge_mask,
              sig (D, M), stats (G*n_dev, 3))

    This is the end-of-step view over the band-group machinery: the
    per-group bounded buffers (G = ``cfg.band_groups``, default 1) are
    concatenated into one edge array whose shard rows are the (group,
    device) buffers in group-major order.  Use
    ``make_streamed_dedup_step`` to consume the groups as a stream
    (overlapped host merge) or for the device-resident stage 2.
    """
    streamed = make_streamed_dedup_step(cfg, mesh, stage2="host")

    @jax.jit
    def dedup_step(tokens, lengths, seeds, doc_offsets=None):
        out = streamed(tokens, lengths, seeds, doc_offsets)
        gs = out["groups"]
        return {
            "edges": jnp.concatenate([g["edges"] for g in gs]),
            "prescreen_sims": jnp.concatenate(
                [g["prescreen_sims"] for g in gs]),
            "edge_mask": jnp.concatenate([g["edge_mask"] for g in gs]),
            "sig": out["sig"],
            "stats": jnp.concatenate([g["stats"] for g in gs]),
        }

    return dedup_step


def dedup_input_specs(cfg: DistLSHConfig, num_docs: int, max_len: int):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    return {
        "tokens": jax.ShapeDtypeStruct((num_docs, max_len), jnp.uint32),
        "lengths": jax.ShapeDtypeStruct((num_docs,), jnp.int32),
        "seeds": jax.ShapeDtypeStruct((cfg.num_hashes,), jnp.uint32),
    }


# ---------------------------------------------------------------------------
# Host-side merge: stage-2 verify + clustering through the shared engine
# ---------------------------------------------------------------------------

@dataclass
class ShardedClusterResult:
    """Outcome of ``cluster_step_output`` (sharded path, host merge)."""

    uf: "ThresholdUnionFind"
    stats: "ClusterStats"
    pairs: "PairList"  # evaluated (a, b, sim) with full-signature sims
    num_edges: int          # stage-1 survivors fed into the engine
    overflow: int           # device bucket/edge-buffer overflow count
    retried: bool           # True when the overflow fallback pass ran
    device_stats: np.ndarray  # (n_dev, 3) [edge_count, candidates, ovf]
    group_stats: list = field(default_factory=list)  # per-band-group
    device_scored: int = 0  # stage-2 pairs served from device scores
    host_rescored: int = 0  # stage-2 pairs re-scored on the host
    row_overflow: int = 0   # cross-shard row-buffer overflow (stage2=device)

    def labels(self) -> np.ndarray:
        return self.uf.components()


@dataclass
class StepFeed:
    """Outcome of ``feed_step_groups`` (one step fed into an accumulator)."""

    num_edges: int
    overflow: int
    row_overflow: int
    device_stats: np.ndarray
    group_stats: list


def _resolve_stream(stream: bool | None) -> bool:
    """Measured-win heuristic for the overlapped band-group merge.

    A committed ``BENCH_smoke.json`` once showed the overlapped merge
    LOSING to the serialized one (``saved_us=-58703``); re-measuring
    with best-of-N timing (single-shot smoke timings on a shared 2-vCPU
    runner swing by tens of ms) shows the overlap reliably *winning*
    ~20-25% even on a 2-core CPU host — the merge is numpy/GIL-bound
    while the shuffle runs on XLA's own thread pool, so the two really
    do overlap.  The auto policy therefore streams everywhere except
    the one configuration that cannot overlap by construction: a
    single-core host running the CPU backend (device compute and host
    merge share the only core, so blocking up front is free and avoids
    per-group sync round-trips).  ``stream=True/False`` forces either
    mode — results are identical — and
    ``benchmarks/designs.run_band_group_overlap`` reports ``saved_us``
    for both forced modes plus this auto policy.
    """
    if stream is not None:
        return bool(stream)
    import os

    if jax.default_backend() != "cpu":
        return True
    return (os.cpu_count() or 1) > 1


def feed_step_groups(
    acc,
    out: dict,
    cfg: DistLSHConfig,
    *,
    num_docs: int,
    edge_offset: int = 0,
    verifier=None,
    stream: bool | None = None,
    on_group_merged=None,
) -> StepFeed:
    """Feed one (streamed) dedup-step output into a ``ClusterAccumulator``.

    The single home of the sharded host-merge plumbing, shared by
    ``cluster_step_output`` (fresh per-step accumulator, chunk-local
    ids) and ``session.DedupSession`` (one long-lived accumulator,
    global ids): per band-group, materialize the bounded edge buffer
    (in stream mode this blocks on THAT group's shuffle only, so the
    merge of group g overlaps the shuffle of group g+1), register
    device-computed stage-2 scores with the verifier, and feed the
    group through the accumulator.  Edge ids are shifted by
    ``edge_offset`` and range-filtered to ``[0, num_docs)``.

    ``on_group_merged`` (if given) runs after each group's feed — the
    session's retention layer sweeps evictions here so memory stays
    bounded even WITHIN a giant step.  The sweep is safe mid-step: it
    only releases rows of docs that lost union-find roothood outside
    its protection window, while the remaining groups' edges — and the
    stage-2 device-score / sig-row-exchange re-score path — reference
    only this step's own (protected) rows and current roots.

    Returns the step's edge/overflow accounting; the overflow fallback
    stays with the caller (it needs the right band source for the ids
    in play).
    """
    from repro.core.candidates import ShardedEdgeSource

    groups = out.get("groups")
    if groups is None:
        # End-of-step view: one (G*n_dev, 3) stats array whose rows are
        # the (group, device) buffers; treat it as a single group.
        groups = [out]
    device_scored = out.get("stage2") == "device"
    if not _resolve_stream(stream):
        jax.block_until_ready([g["edges"] for g in groups])
    m = out["sig"].shape[1]

    num_edges = 0
    row_overflow = 0
    group_stats = []
    device_stats_parts = []
    for g_out in groups:
        # Materializing this group's buffers blocks on ITS shuffle only;
        # later groups keep running on the device meanwhile.  Ids
        # outside [0, num_docs) after the edge_offset shift (padding,
        # INVALID slots, other chunks' docs) are dropped by the
        # source's range filter.
        g_stats = np.asarray(g_out["stats"])
        device_stats_parts.append(g_stats)
        source = ShardedEdgeSource.from_device_buffers(
            g_out["edges"], g_out["edge_mask"], num_docs=num_docs,
            num_shards=g_stats.shape[0], edge_offset=edge_offset)
        if device_scored and hasattr(verifier, "add_scores"):
            # Host-side /M of the device match counts: numpy float32
            # division is correctly rounded, so these scores are
            # bit-identical to the host estimator.  ``covered`` spans
            # same-shard edges plus the cross-shard edges scored via
            # the exchanged row buffers; only row-buffer overflow is
            # left for the host re-score path.
            edges = np.asarray(g_out["edges"]).astype(np.int64) - int(
                edge_offset)
            mask = np.asarray(g_out["edge_mask"])
            sims = (np.asarray(g_out["device_match_counts"])
                    / np.float32(m))
            covered = np.asarray(g_out["device_covered"])
            reg = (mask & covered
                   & (edges >= 0).all(axis=-1)
                   & (edges < num_docs).all(axis=-1))
            verifier.add_scores(edges[reg], sims[reg])
            row_overflow += int(
                np.asarray(g_out.get("row_overflow", 0)).sum())
        num_edges += source.num_edges
        group_stats.append(acc.feed(source, verifier=verifier))
        if on_group_merged is not None:
            on_group_merged()

    if device_scored and hasattr(verifier, "clear_scores"):
        # Registered scores are dead once their edges have been fed
        # (sim cache / co-clustering make re-lookup impossible); keep
        # the long-lived session registry from growing per step.
        verifier.clear_scores()

    device_stats = np.concatenate(device_stats_parts)
    return StepFeed(
        num_edges=num_edges,
        overflow=int(device_stats[:, 2].sum()),
        row_overflow=row_overflow,
        device_stats=device_stats,
        group_stats=group_stats)


def cluster_step_output(
    out: dict,
    cfg: DistLSHConfig,
    *,
    tree_threshold: float = 0.40,
    backend: str = "numpy",
    batch: str = "run",
    num_docs: int | None = None,
    doc_id_base: int = 0,
    overflow_fallback: bool = True,
    batch_pairs: int = 8192,
    stream: bool | None = None,
) -> ShardedClusterResult:
    """Stage 2 of the sharded path: batched full-signature verify + merge.

    Accepts either the end-of-step output of ``make_dedup_step`` or the
    band-group stream of ``make_streamed_dedup_step`` (a ``"groups"``
    key).  In stream mode each group's buffers are materialized only
    when the engine reaches them and fed incrementally through one
    ``engine.ClusterAccumulator`` — the host merge of group g overlaps
    the device shuffle of group g+1, and a pair verified for group g is
    excluded (never re-verified) when group g+1 emits it again.

    Drives the prescreened edges through the shared staged engine —
    ``ShardedEdgeSource`` -> ``ShardedEdgeVerifier`` (full (D, M)
    signatures, same numpy/jnp/pallas backends as the host path) ->
    ``engine.cluster_source`` — so edge thresholds, estimator semantics,
    and exclusion stats are identical to ``DedupPipeline``.  For
    ``stage2="device"`` step outputs the verifier is a
    ``DeviceScoredEdgeVerifier``: same-shard edges were already scored
    on the accelerator and pass straight through; only cross-shard
    stragglers (and post-union root pairs) hit the host estimator.

    ``num_docs`` bounds real documents: edges touching padding rows
    (appended for divisibility by the device count) are dropped.

    ``doc_id_base`` must echo the base passed to the step via
    ``doc_offsets`` when a chunk of a larger corpus was processed: edge
    ids are global (``doc_id_base + row``) while ``sig`` rows are
    chunk-local, so the merge shifts edges back before verification.
    All returned ids (uf labels, pairs) are chunk-local row indices;
    add ``doc_id_base`` to map them back into the global corpus.

    If any device overflowed a bucket or its edge buffer, prescreen
    edges were lost on device; with ``overflow_fallback`` the merge
    re-derives candidates on the host from the step's own signatures
    (``BandMatrixSource`` over ``lsh.band_values``) and accumulates them
    through the SAME engine into the same union-find, so no candidate
    is silently dropped.

    ``stream`` controls whether groups are consumed lazily (overlapped
    merge) or after blocking on every buffer; the default defers to the
    measured-win heuristic (see ``_resolve_stream``) — results are
    identical either way.

    This is the one-shot adapter over the session-grade merge plumbing
    (``feed_step_groups``); incremental multi-step ingest goes through
    ``core.session.DedupSession`` instead, which feeds many step
    outputs into ONE accumulator.
    """
    from repro.core.candidates import BandMatrixSource
    from repro.core.engine import ClusterAccumulator
    from repro.core.verify import (DeviceScoredEdgeVerifier,
                                   ShardedEdgeVerifier)

    sig = np.asarray(out["sig"])
    num_docs = sig.shape[0] if num_docs is None else int(num_docs)

    cls = (DeviceScoredEdgeVerifier if out.get("stage2") == "device"
           else ShardedEdgeVerifier)
    verifier = cls(sig[:num_docs], backend=backend,
                   batch_pairs=batch_pairs)
    acc = ClusterAccumulator(
        num_docs, verifier, cfg.edge_threshold, tree_threshold,
        batch=batch)

    feed = feed_step_groups(
        acc, out, cfg, num_docs=num_docs, edge_offset=doc_id_base,
        verifier=verifier, stream=stream)

    retried = False
    if feed.overflow > 0 and overflow_fallback:
        retried = True
        bands = np.asarray(
            band_values(jnp.asarray(sig[:num_docs]), cfg.rows_per_band))
        acc.feed(BandMatrixSource(bands))

    return ShardedClusterResult(
        uf=acc.uf, stats=acc.stats, pairs=acc.pairs,
        num_edges=feed.num_edges, overflow=feed.overflow,
        retried=retried, device_stats=feed.device_stats,
        group_stats=feed.group_stats,
        device_scored=getattr(verifier, "n_passthrough", 0),
        host_rescored=getattr(verifier, "n_rescored", 0),
        row_overflow=feed.row_overflow)
