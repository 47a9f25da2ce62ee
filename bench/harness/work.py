"""The scan work a ``search`` call's queries need, fixed by the queries and
the deployment, never by the implementation or the plan it picks.

One row is the stored slab row: the vector at the storage type plus its fp32
squared norm (and, for int8, its fp32 scale).

- flat: one read of all n rows, 2 d Q n flops;
- IVF: the rows of the expected union of the lists Q queries probe,
  nlist (1 - (1 - nprobe / nlist)^Q) lists of n / nlist rows each, and
  2 d Q nprobe n / nlist flops;
- predicate: the eligible rows only, 2 d Q n_elig flops.

The least time of that work on a chip is max(bytes / peak bytes/s,
flops / peak flop/s).
"""
from __future__ import annotations

ELEM_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


def row_bytes(cfg: dict) -> int:
    st = cfg["fcvi"].get("storage_dtype", "float32")
    extra = 8 if st == "int8" else 4
    return ELEM_BYTES[st] * int(cfg["d"]) + extra


def scan_work(cfg: dict, mode: str, q: int, n: int, n_elig: int = 0):
    """(bytes, flops) of the scan for one call of ``q`` queries over ``n``
    rows (``n_elig`` of them eligible in predicate mode)."""
    d = int(cfg["d"])
    rb = row_bytes(cfg)
    if mode == "predicate":
        return n_elig * rb, 2.0 * d * q * n_elig
    if cfg["fcvi"]["backend"] == "ivf":
        nlist, nprobe = int(cfg["nlist"]), int(cfg["nprobe"])
        lists = nlist * (1.0 - (1.0 - nprobe / nlist) ** q)
        return lists * (n / nlist) * rb, 2.0 * d * q * nprobe * n / nlist
    return n * rb, 2.0 * d * q * n


def least_time(bytes_, flops, peak: dict) -> float:
    return max(bytes_ / peak["hbm_bytes_per_s"], flops / peak["flops_per_s"])
