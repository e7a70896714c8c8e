"""Roofline analysis over the dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape), single-pod mesh, TPU v5e constants:

  compute_s    = HLO_FLOPs_per_chip / 197e12        (bf16 MXU peak)
  memory_s     = HLO_bytes_per_chip / 819e9          (HBM)
  collective_s = collective_bytes_per_chip / 50e9    (ICI link)

HLO numbers come from ``compiled.cost_analysis()`` / the HLO-text collective
parser on the SPMD-partitioned per-device module.  XLA counts a ``lax.scan``
body ONCE, so LM cells carry a two-point probe (G=1 and G=2 layer groups);
the exact per-device total is the linear extrapolation
``m1 + (n_groups - 1) * (m2 - m1)`` (layer groups are homogeneous by
construction).  Cells without scans (recsys/gnn) need no correction.  The
WTBC cells' while-loops are data-dependent: the analysis reports
per-candidate-iteration cost x the expected iteration count.

MODEL_FLOPS (the "useful work" numerator for the compute-fraction score) is
analytic: 6·N·T for dense-LM training (6·N_active·T for MoE) plus exact
attention-window terms, 2·N·T for inference; per-tower closed forms for
recsys; per-layer closed forms for EGNN.

**WTBC query-path model** (ISSUE 8, DESIGN.md §9): the search loop is pure
memory traffic — every rank probe reads one counter-block tile plus a
counter entry, and Algorithm 1 issues ``2 ranks × levels × Q`` probes per
popped (or padded) beam lane.  ``wtbc_query_roofline`` turns measured
pops/padded/latency into bytes/query and an achieved-fraction-of-peak
against the device's HBM bandwidth (``launch/mesh.CHIP_PEAKS``, keyed by
device kind) — the number benchmarks/table5 reports next to each beam cell.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib

import numpy as np

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16, chip_peak

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS
# ---------------------------------------------------------------------------

def _lm_attn_flops(cfg, B, S, decode=False):
    """Sum over layers of QK^T + PV flops (fwd)."""
    total = 0.0
    for i, pat in enumerate(cfg.pattern):
        n = cfg.n_layers // len(cfg.pattern)
        if decode:
            span = S if pat == "global" or cfg.window == 0 else min(cfg.window, S)
            total += n * 4.0 * B * span * cfg.n_heads * cfg.head_dim
        else:
            if pat == "global" or cfg.window == 0 or cfg.window >= S:
                span = S / 2
            elif pat == "local":
                span = cfg.window
            else:                       # chunked: average window/2
                span = cfg.window / 2
            total += n * 4.0 * B * S * span * cfg.n_heads * cfg.head_dim
    return total


def lm_model_flops(cfg, shape_meta: dict, kind: str) -> float:
    B, S = shape_meta["batch"], shape_meta["seq"]
    N_act = cfg.active_param_count()
    if kind == "train":
        T = B * S
        fwd = 2.0 * N_act * T + _lm_attn_flops(cfg, B, S)
        factor = 3.0 + (1.0 if cfg.remat else 0.0)   # fwd+2bwd (+refwd remat)
        return factor * fwd
    if kind == "prefill":
        return 2.0 * N_act * B * S + _lm_attn_flops(cfg, B, S)
    # decode: one token, full KV span
    return 2.0 * N_act * B + _lm_attn_flops(cfg, B, S, decode=True)


def recsys_model_flops(cfg, B: int, kind: str) -> float:
    d = cfg.embed_dim
    f = 0.0
    if cfg.interaction == "fm":
        f = 4.0 * B * cfg.n_sparse * d
    elif cfg.interaction == "cin":
        dims = (cfg.n_sparse,) + cfg.cin_layers
        for i in range(len(cfg.cin_layers)):
            f += 2.0 * B * dims[i + 1] * dims[i] * cfg.n_sparse * d \
                 + 2.0 * B * dims[i] * cfg.n_sparse * d
        flat = cfg.n_sparse * d
        f += 2.0 * B * (flat * 400 + 400 * 400 + flat)
    elif cfg.interaction == "dot":
        dims = (cfg.n_dense,) + cfg.bot_mlp
        f += 2.0 * B * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
        nf = cfg.n_sparse + 1
        f += 2.0 * B * nf * nf * d
        n_inter = nf * (nf - 1) // 2
        tdims = (cfg.bot_mlp[-1] + n_inter,) + cfg.top_mlp
        f += 2.0 * B * sum(a * b for a, b in zip(tdims[:-1], tdims[1:]))
    elif cfg.interaction == "self-attn-seq":
        S = cfg.seq_len
        per_blk = 2.0 * B * S * d * d * 6 + 4.0 * B * S * S * d / 2
        f = cfg.n_blocks * per_blk
    if kind == "train":
        f *= 3.0
    return f


def egnn_model_flops(cfg, n_nodes: int, n_edges: int, kind: str) -> float:
    H = cfg.d_hidden
    per_layer = (2.0 * n_edges * ((2 * H + 1) * H + H * H)      # phi_e
                 + 2.0 * n_edges * (H * H + H)                  # phi_x
                 + 2.0 * n_nodes * (2 * H * H + H * H))         # phi_h
    f = cfg.n_layers * per_layer + 2.0 * n_nodes * cfg.d_feat * H
    return f * (3.0 if kind == "train" else 1.0)


# ---------------------------------------------------------------------------
# artifact reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CellRoofline:
    cell: str
    kind: str
    chips: int
    hlo_flops: float             # per-chip, scan-corrected (XLA-CPU caveat:
                                 # oneDNN custom-call matmuls report 0 flops,
                                 # so this UNDERCOUNTS — reported for trend
                                 # tracking only)
    bytes_hbm: float             # per-chip, scan-corrected
    coll_bytes: float            # per-chip, scan-corrected
    compute_s: float             # analytic MODEL_FLOPS / chip / peak
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_per_chip: float
    peak_mem_gb: float | None
    skipped: str | None = None

    def step_time(self) -> float:
        """No-overlap upper bound (the three terms serialized)."""
        return self.compute_s + self.memory_s + self.collective_s

    def roofline_fraction(self) -> float:
        """useful-compute share of the binding resource:
        compute_s / max(compute_s, memory_s, collective_s).
        1.0 = the cell is bound by useful MXU work (at roofline); lower
        values = memory or collective time exceeds useful compute."""
        m = max(self.compute_s, self.memory_s, self.collective_s, 1e-30)
        return self.compute_s / m


def _probe_total(rec: dict, metric_path, n_groups: int) -> float | None:
    try:
        m1 = metric_path(rec["probe_g1"])
        m2 = metric_path(rec["probe_g2"])
    except KeyError:
        return None
    return m1 + (n_groups - 1) * (m2 - m1)


def reduce_cell(rec: dict, model_flops_total: float | None) -> CellRoofline:
    if rec.get("skipped"):
        return CellRoofline(cell=rec["cell"], kind="-", chips=0, hlo_flops=0,
                            bytes_hbm=0, coll_bytes=0, compute_s=0, memory_s=0,
                            collective_s=0, dominant="-",
                            model_flops_per_chip=0, peak_mem_gb=None,
                            skipped=rec["skipped"])
    chips = int(np.prod(list(rec["mesh_shape"].values())))
    G = rec.get("n_groups")
    flops = rec["cost_analysis"].get("flops", 0.0)
    hbm = rec["cost_analysis"].get("bytes accessed", 0.0)
    coll = rec["collectives"]["total_bytes"]
    if G and "probe_g1" in rec:
        flops = _probe_total(rec, lambda p: p["cost_analysis"].get("flops", 0.0), G) or flops
        hbm = _probe_total(rec, lambda p: p["cost_analysis"].get("bytes accessed", 0.0), G) or hbm
        coll = _probe_total(rec, lambda p: p["collectives"]["total_bytes"], G) or coll
    mf = (model_flops_total or 0.0) / chips
    flops, hbm, coll = max(flops, 0.0), max(hbm, 0.0), max(coll, 0.0)  # probe
    # extrapolation can go slightly negative when XLA CSEs across group counts
    compute_s = max(mf, flops) / PEAK_FLOPS_BF16   # analytic useful compute
    memory_s = hbm / HBM_BW
    collective_s = coll / ICI_BW
    dom = max(("compute", compute_s), ("memory", memory_s),
              ("collective", collective_s), key=lambda kv: kv[1])[0]
    peak = rec.get("memory_analysis", {}).get("peak_memory_in_bytes")
    return CellRoofline(
        cell=rec["cell"], kind=rec.get("kind", "?"), chips=chips,
        hlo_flops=flops, bytes_hbm=hbm, coll_bytes=coll,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dom, model_flops_per_chip=mf,
        peak_mem_gb=(peak / 2**30 if peak else None))


def model_flops_for(cell_id: str, kind: str) -> float | None:
    from repro.configs import registry
    from repro.configs.lm_common import LM_SHAPES
    from repro.configs import recsys_common, egnn as egnn_cfg
    arch_name, shape = cell_id.split(":")
    if arch_name == "wtbc":
        return None
    arch = registry.get(arch_name)
    cfg = arch.config_for(shape)
    if arch.family == "lm":
        return lm_model_flops(cfg, LM_SHAPES[shape], kind)
    if arch.family == "recsys":
        if shape == "retrieval_cand":
            return recsys_model_flops(cfg, recsys_common.N_CANDIDATES, "serve")
        B = recsys_common.SHAPES[shape]["batch"]
        return recsys_model_flops(cfg, B, kind)
    if arch.family == "gnn":
        m = egnn_cfg.SHAPES[shape]
        return egnn_model_flops(cfg, m["nodes"], m["edges"], kind)
    return None


def load_all(mesh_name: str = "single") -> list[CellRoofline]:
    out = []
    for path in sorted((ART / mesh_name).glob("*.json")):
        rec = json.loads(path.read_text())
        if not rec.get("ok"):
            continue
        kind = rec.get("kind", "?")
        mf = model_flops_for(rec["cell"], kind) if ":" in rec["cell"] else None
        out.append(reduce_cell(rec, mf))
    return out


def markdown_table(rows: list[CellRoofline]) -> str:
    hdr = ("| cell | kind | compute_s | memory_s | collective_s | dominant | "
           "roofline frac | peak GB |\n"
           "|---|---|---|---|---|---|---|---|\n")
    lines = []
    for r in rows:
        if r.skipped:
            lines.append(f"| {r.cell} | skip | - | - | - | - | - | - |")
            continue
        lines.append(
            f"| {r.cell} | {r.kind} | {r.compute_s:.2e} | {r.memory_s:.2e} | "
            f"{r.collective_s:.2e} | **{r.dominant}** | "
            f"{r.roofline_fraction():.3f} | "
            f"{'' if r.peak_mem_gb is None else f'{r.peak_mem_gb:.1f}'} |")
    return hdr + "\n".join(lines)


# ---------------------------------------------------------------------------
# WTBC query-path roofline (DESIGN.md §9)
# ---------------------------------------------------------------------------

# Per-rank counter traffic by descent lowering kind: the TPU kernel DMAs the
# aligned 8-row group of (256,) int32 counter rows holding the entry; the
# GPU/ref lowerings gather one 4-byte entry.
WTBC_COUNTER_BYTES: dict[str, float] = {"tpu": 8 * 256 * 4.0, "gpu": 4.0,
                                        "ref": 4.0}


def wtbc_query_bytes(*, pops: float, padded: float, q: int, block: int,
                     levels: int = 3,
                     counter_bytes: float = 4.0) -> float:
    """Bytes the WTBC query path must move per query.

    Every popped beam lane (plus every padded dead lane — the hardware reads
    for those too, which is exactly why table5 tracks pad waste) descends all
    ``levels`` of the wavelet tree for each of the ``q`` query words, and each
    level's ``count_range`` issues 2 rank probes.  A probe touches one
    ``block``-byte counter-block tile plus ``counter_bytes`` of superblock
    counters; the tiny node-offset/codeword tables are shared across probes
    and amortize to ~0.
    """
    ranks = 2.0 * levels * q * (pops + padded)
    return ranks * (block + counter_bytes)


@dataclasses.dataclass
class WTBCQueryRoofline:
    """Memory-roofline attachment for one table5 beam cell."""
    device_kind: str              # device the bandwidth peak is read for
    lowering: str                 # descent plan tag (counter-traffic shape)
    bytes_per_query: float
    model_us_per_query: float     # bytes / BW — the memory-bound floor
    measured_us_per_query: float
    achieved_frac: float          # model / measured; 1.0 = at the roofline,
                                  # small values = launch/loop overhead bound


def wtbc_query_roofline(*, device_kind: str, lowering: str,
                        measured_us_per_query: float,
                        pops: float, padded: float, q: int, block: int,
                        levels: int = 3) -> WTBCQueryRoofline:
    """Attach the bytes/query model to a measured per-query latency.

    ``pops``/``padded`` are per-query means (floats are fine);
    ``device_kind`` (``jax.Device.device_kind``) picks the bandwidth peak
    from ``launch/mesh.CHIP_PEAKS`` — an unknown kind raises; ``lowering``
    (``KernelPlan.tag``) picks the counter-traffic shape.
    """
    cb = WTBC_COUNTER_BYTES[lowering.partition(":")[0]]
    bpq = wtbc_query_bytes(pops=pops, padded=padded, q=q, block=block,
                           levels=levels, counter_bytes=cb)
    bw = chip_peak(device_kind, "hbm_bw")
    model_us = bpq / bw * 1e6
    frac = model_us / max(measured_us_per_query, 1e-9)
    return WTBCQueryRoofline(device_kind=device_kind, lowering=lowering,
                             bytes_per_query=bpq,
                             model_us_per_query=model_us,
                             measured_us_per_query=measured_us_per_query,
                             achieved_frac=frac)


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    rows = load_all(args.mesh)
    print(markdown_table(rows))
    if args.json_out:
        pathlib.Path(args.json_out).write_text(json.dumps(
            [dataclasses.asdict(r) for r in rows], indent=1))


if __name__ == "__main__":
    main()
