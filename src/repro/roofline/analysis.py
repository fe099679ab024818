"""Roofline analysis over dry-run artifacts.

Hardware model: the per-chip peaks of ``PEAKS``, keyed by
``jax.Device.device_kind``.  Dry-run cells (production meshes of v5e) use
the v5e entry.  Terms per (arch × shape × mesh) cell:

  t_comp = parsed_FLOPs_per_device / peak FLOP/s
  t_mem  = parsed_HBM_bytes_per_device / HBM bytes/s
  t_coll = parsed_collective_bytes_per_device / link bytes/s

The bottleneck is the max term; roofline fraction = t_comp / max(terms)
(the share of the step the MXUs could actually be busy).  MODEL_FLOPS
(6·N·D or 6·N_active·D) cross-checks the parsed FLOPs — the ratio catches
remat/redundancy waste in the compiled module.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, List, NamedTuple, Optional, Tuple

from repro.configs import SHAPES, get_config
from repro.roofline.hlo_parse import analyze


class Peaks(NamedTuple):
    flops: float             # FLOP/s per chip (bf16 for TPUs)
    mem_bw: float            # memory bytes/s per chip
    link_bw: float           # bytes/s per interconnect link
    mem_cap: float           # memory bytes per chip
    source: str


V5E = "TPU v5 lite"          # jax's device_kind for a TPU v5e chip

PEAKS: Dict[str, Peaks] = {
    # 1,600 Gbit/s of ICI per chip over 4 links: 50 GB/s per link
    V5E: Peaks(197e12, 819e9, 50e9, 16 * 2 ** 30,
               'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 16 GB HBM, 1,600 Gbit/s ICI'),
    # not a device peak: nominal per-core host figures so the seg-scan
    # autotuner can rank candidates in CPU test runs, where only the
    # flops:bandwidth RATIO decides the chosen chunk
    "cpu": Peaks(5e10, 2e10, 1e10, 64 * 2 ** 30,
                 "nominal host CPU core, for ranking in tests only"),
}


def peaks(device_kind: str) -> Peaks:
    """The peak table entry for a ``jax.Device.device_kind``.  A kind that
    is not in the table is an error: a roofline against another chip's
    peaks would be a wrong number, not an estimate."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add it "
            f"to repro.roofline.analysis.PEAKS with its source") from None


def local_device_kind() -> str:
    """``device_kind`` of the first local device — what this process runs
    on, and the key the autotuner persists its choices under."""
    import jax
    return jax.devices()[0].device_kind


def roofline_terms(costs, device_kind: Optional[str] = None
                   ) -> Tuple[float, float, float, str]:
    """(t_comp, t_mem, t_coll, bottleneck) for a ``hlo_parse.Costs`` — the
    same max-term model ``analyze_cell`` applies to dry-run artifacts,
    reusable on directly-parsed (or analytically-modelled) costs.  This is
    what the seg-scan chunk autotuner ranks candidates with."""
    peak, mem_bw, link_bw, _, _ = peaks(device_kind or local_device_kind())
    t_comp = costs.flops / peak
    t_mem = costs.hbm_bytes / mem_bw
    t_coll = costs.coll_bytes / link_bw
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    return t_comp, t_mem, t_coll, max(terms, key=terms.get)


@dataclasses.dataclass
class CellRoofline:
    arch: str
    shape: str
    mesh: str
    tag: str
    n_devices: int
    flops_per_dev: float
    hbm_bytes_per_dev: float
    coll_bytes_per_dev: float
    coll_by_kind: Dict[str, float]
    t_comp: float
    t_mem: float
    t_coll: float
    bottleneck: str
    roofline_fraction: float
    model_flops: float
    useful_ratio: float        # MODEL_FLOPS / (parsed_flops × devices)
    peak_gb: float
    fits_hbm: bool
    meta: Dict

    def row(self) -> str:
        return (f"| {self.arch} | {self.shape} | {self.mesh}"
                f"{('/' + self.tag) if self.tag else ''} | "
                f"{self.t_comp * 1e3:.2f} | {self.t_mem * 1e3:.2f} | "
                f"{self.t_coll * 1e3:.2f} | {self.bottleneck} | "
                f"{self.roofline_fraction * 100:.0f}% | "
                f"{self.useful_ratio * 100:.0f}% | {self.peak_gb:.1f} | "
                f"{'✓' if self.fits_hbm else '✗'} |")


def model_flops_for(arch: str, shape_name: str) -> float:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def branch_weights_for(arch: str) -> Optional[List[float]]:
    cfg = get_config(arch)
    if cfg.global_interval > 0:
        kinds = cfg.layer_kinds()
        g = sum(k == "attn_global" for k in kinds) / len(kinds)
        # jax.lax.cond lowers pred branches as (false, true)
        return [1.0 - g, g]
    return None


def analyze_cell(json_path: str) -> CellRoofline:
    import zstandard as zstd    # optional dep: only dry-run artifacts use it

    meta = json.load(open(json_path))
    hlo_path = json_path.replace(".json", ".hlo.zst")
    txt = zstd.ZstdDecompressor().decompress(
        open(hlo_path, "rb").read()).decode()
    arch, shape, mesh = meta["arch"], meta["shape"], meta["mesh"]
    costs = analyze(txt, branch_weights=branch_weights_for(arch))
    n_dev = 512 if mesh == "pod2" else 256

    t_comp, t_mem, t_coll, bottleneck = roofline_terms(costs, V5E)
    t_max = max(t_comp, t_mem, t_coll) or 1e-30

    mf = model_flops_for(arch, shape)
    parsed_total = costs.flops * n_dev
    return CellRoofline(
        arch=arch, shape=shape, mesh=mesh, tag=meta.get("tag", ""),
        n_devices=n_dev,
        flops_per_dev=costs.flops, hbm_bytes_per_dev=costs.hbm_bytes,
        coll_bytes_per_dev=costs.coll_bytes,
        coll_by_kind=dict(costs.coll_by_kind),
        t_comp=t_comp, t_mem=t_mem, t_coll=t_coll, bottleneck=bottleneck,
        roofline_fraction=t_comp / t_max,
        model_flops=mf, useful_ratio=mf / parsed_total if parsed_total else 0.0,
        peak_gb=meta.get("peak_gb", 0.0),
        fits_hbm=meta.get("peak_gb", 0.0) <= PEAKS[V5E].mem_cap / 2 ** 30,
        meta=meta)


HEADER = ("| arch | shape | mesh | t_comp (ms) | t_mem (ms) | t_coll (ms) | "
          "bottleneck | roofline | useful | GB/dev | fits |\n"
          "|---|---|---|---|---|---|---|---|---|---|---|")


def analyze_dir(dry_dir: str, mesh: str = "pod1", tag: str = "") -> List[CellRoofline]:
    cells = []
    for jp in sorted(glob.glob(os.path.join(dry_dir, f"*_{mesh}"
                                            f"{('_' + tag) if tag else ''}"
                                            ".json"))):
        try:
            cells.append(analyze_cell(jp))
        except Exception as e:              # pragma: no cover
            print(f"[roofline] failed {jp}: {e!r}")
    return cells


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="experiments/dryrun")
    ap.add_argument("--mesh", default="pod1")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()
    cells = analyze_dir(args.dir, args.mesh, args.tag)
    print(HEADER)
    for c in cells:
        print(c.row())


if __name__ == "__main__":
    main()
