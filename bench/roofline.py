"""Operations and bytes of the Pallas kernels the benchmark reads, from
their shapes, and the table of device peaks.

A kernel's roofline share is the least time the chip could take for
the work, the larger of operations over peak FLOP/s and bytes over
peak bandwidth, divided by the kernel's measured time.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Tuple

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Dict[str, float]:
    """Peaks of ``device_kind``; a kind not in the table is an error."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def cov_update_cost(d: int, ipf: int, itemsize: int = 4
                    ) -> Tuple[float, float]:
    """(operations, bytes) of one ``cov_update`` call on one robot: the
    covariance P is (d, d), ``ipf`` IMU samples each apply a (15, 15)
    transition F.

    Per sample: F Pii F^T is two 15x15x15 matmuls, F Pic one
    15x15x(d-15) matmul (2 operations per multiply-add); adding Q and
    the symmetrising 0.5 (A + A^T) are 3 x 225 more. The closing
    augment only moves data. Bytes: P is read once and written once
    (it stays in fast memory across the sweep), F_seq, Q and the gate
    are read once."""
    per_sample = 2 * 2 * 15 ** 3 + 2 * 15 * 15 * (d - 15) + 3 * 15 * 15
    ops = ipf * per_sample
    nbytes = itemsize * (2 * d * d + ipf * 15 * 15 + 15 * 15 + 1)
    return float(ops), float(nbytes)


def roofline_share(ops: float, nbytes: float, seconds: float,
                   peak: Dict[str, float]) -> Tuple[float, str]:
    """(share of the roofline in %, the bound that applies)."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_bytes >= t_ops else "compute"
    return 100.0 * max(t_ops, t_bytes) / seconds, bound
