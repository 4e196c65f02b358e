"""Operations and bytes that each unit of BCPNN work requires, counted
from the algorithm's shapes -- never from what today's kernels move --
and the chip peaks they are held against (``peaks.json``).

A count here is a floor that any implementation must pay, so a share of
a roofline or of a peak computed from it cannot pass 100%:

* a learn step on an (Ni, Nj) projection reads and writes the float32
  joint trace once (8 Ni Nj bytes) and its activations, and does the
  co-activation product (2 B Ni Nj operations).  The weights
  w = log(p_ij / (p_i p_j)) are a function of the trace read in the same
  pass, so neither a stored ``w``, a unit mask nor padding is counted;
* a forward (activation) reads the weights once in the serving dtype and
  does the support product (2 B Ni Nj operations);
* softmax epilogues and trace arithmetic are O(Ni Nj) elementwise work,
  orders of magnitude under the products, and are not counted.

Operations counted at float32 or bfloat16 are held against the bf16
peak, those counted at int8 against the int8 peak.
"""
from __future__ import annotations

import dataclasses
import json
import os

F32 = 4
DTYPE_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float = 0.0
    bytes: float = 0.0
    int8_ops: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes,
                    self.int8_ops + o.int8_ops)

    def __mul__(self, n: float) -> "Work":
        return Work(self.flops * n, self.bytes * n, self.int8_ops * n)

    __rmul__ = __mul__


def learn(ni: int, nj: int, b: int) -> Work:
    """One plasticity step of a projection over ``b`` genuine rows."""
    return Work(flops=2.0 * b * ni * nj,
                bytes=2.0 * F32 * ni * nj + F32 * b * (ni + nj))


def forward(ni: int, nj: int, b: int, dtype: str = "fp32") -> Work:
    """One activation of a projection over ``b`` rows, weights read once."""
    if dtype == "int8":
        return Work(int8_ops=2.0 * b * ni * nj,
                    bytes=ni * nj + F32 * b * (ni + nj))
    return Work(flops=2.0 * b * ni * nj,
                bytes=DTYPE_BYTES[dtype] * ni * nj + F32 * b * (ni + nj))


def unsup_step(ni: int, nj: int, b: int) -> Work:
    """Unsupervised step on the hidden projection: the noisy forward
    reads the weights that the learn's own trace pass yields, so only its
    product is added to the learn."""
    return learn(ni, nj, b) + Work(flops=2.0 * b * ni * nj)


def sup_step(ni: int, nj: int, k: int, b: int) -> Work:
    """Supervised readout step: hidden forward, then the readout learn."""
    return forward(ni, nj, b) + learn(nj, k, b)


def served_group(ni: int, nj: int, k: int, n: int,
                 dtype: str = "fp32") -> Work:
    """One served microbatch of ``n`` genuine requests."""
    return forward(ni, nj, n, dtype) + forward(nj, k, n, dtype)


def model_flops_train(ni: int, nj: int, k: int) -> tuple:
    """Model operations per image: (unsupervised epoch, readout pass)."""
    return 4.0 * ni * nj, 2.0 * ni * nj + 2.0 * nj * k


def model_flops_served(ni: int, nj: int, k: int) -> float:
    return 2.0 * ni * nj + 2.0 * nj * k


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peak table's row for ``device_kind``; a device that is not in
    the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def roofline_s(w: Work, peak: dict, chips: int = 1) -> float:
    """Least time ``chips`` chips could take for ``w``: the larger of the
    compute and the memory bound."""
    return max(w.flops / peak["bf16_flops"] + w.int8_ops / peak["int8_ops"],
               w.bytes / peak["hbm_bytes_per_s"]) / chips


def served(ni: int, nj: int, k: int, groups: float, images: float,
           dtype: str = "fp32") -> Work:
    """``images`` requests served in ``groups`` microbatches: each group
    reads the weights once, each image adds its products and rates."""
    per_group = forward(ni, nj, 0, dtype) + forward(nj, k, 0, dtype)
    per_image = forward(ni, nj, 1, dtype) + forward(nj, k, 1, dtype)
    return per_group * groups + Work(
        flops=per_image.flops, int8_ops=per_image.int8_ops,
        bytes=per_image.bytes - per_group.bytes) * images
