"""DNN layer workload representation (paper Fig. 1).

Every supported layer is an instance of the 8-nested-loop form

    for b, g, ox, oy, k, c, fx, fy:
        O[b][g][k][ox][oy] += I[b][g][c][ox+fx][oy+fy] * W[k][g][c][fx][fy]

with the specializations of Fig. 1's table:

    Conv2D:     G=1
    Depthwise:  K=1, C=1, G=channels
    Pointwise:  FX=FY=1, G=1
    Dense:      OX=OY=FX=FY=1, G=1

The tinyMLPerf benchmark networks used in the paper's Sec. VI case study
(DeepAutoEncoder, ResNet8, DS-CNN, MobileNetV1) are provided as layer
lists, as is a lowering of transformer blocks (the assigned LM
architectures) into Dense MVM workloads — the beyond-paper extension.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Iterable, Mapping

LOOP_DIMS = ("B", "G", "K", "C", "OX", "OY", "FX", "FY")


@dataclasses.dataclass(frozen=True)
class Layer:
    """One 8-nested-loop layer instance."""

    name: str
    layer_type: str                      # conv2d|depthwise|pointwise|dense
    dims: Mapping[str, int]              # loop bounds, defaults 1
    w_prec: int = 4                      # weight bits
    i_prec: int = 4                      # input bits
    psum_prec: int = 24                  # partial-sum bits in outer memory
    imc_eligible: bool = True            # False for non-MVM compute (scans)

    def dim(self, d: str) -> int:
        return int(self.dims.get(d, 1))

    @property
    def macs(self) -> int:
        out = 1
        for d in LOOP_DIMS:
            out *= self.dim(d)
        return out

    @property
    def weight_elems(self) -> int:
        return (self.dim("G") * self.dim("K") * self.dim("C")
                * self.dim("FX") * self.dim("FY"))

    @property
    def input_elems(self) -> int:
        ix = self.dim("OX") + self.dim("FX") - 1
        iy = self.dim("OY") + self.dim("FY") - 1
        return self.dim("B") * self.dim("G") * self.dim("C") * ix * iy

    @property
    def output_elems(self) -> int:
        return (self.dim("B") * self.dim("G") * self.dim("K")
                * self.dim("OX") * self.dim("OY"))

    @property
    def accumulation_depth(self) -> int:
        """C*FX*FY — the reduction the IMC array performs along its rows."""
        return self.dim("C") * self.dim("FX") * self.dim("FY")


def conv2d(name, b, c_in, k_out, ox, oy, fx, fy, stride=1, **kw) -> Layer:
    # Post-stride output size is what the loop bounds describe.
    return Layer(name, "conv2d",
                 dict(B=b, K=k_out, C=c_in, OX=ox // stride, OY=oy // stride,
                      FX=fx, FY=fy), **kw)


def depthwise(name, b, channels, ox, oy, fx, fy, stride=1, **kw) -> Layer:
    return Layer(name, "depthwise",
                 dict(B=b, G=channels, OX=ox // stride, OY=oy // stride,
                      FX=fx, FY=fy), **kw)


def pointwise(name, b, c_in, k_out, ox, oy, **kw) -> Layer:
    return Layer(name, "pointwise", dict(B=b, K=k_out, C=c_in, OX=ox, OY=oy),
                 **kw)


def dense(name, b, c_in, k_out, **kw) -> Layer:
    return Layer(name, "dense", dict(B=b, K=k_out, C=c_in), **kw)


# --------------------------------------------------------------------------- #
# tinyMLPerf benchmark networks (paper Fig. 1 operator breakdown / Sec. VI)    #
# --------------------------------------------------------------------------- #
def deep_autoencoder(batch: int = 1) -> list[Layer]:
    """MLPerf-tiny anomaly detection FC-AutoEncoder (640-128x4-8-128x4-640)."""
    widths = [640, 128, 128, 128, 128, 8, 128, 128, 128, 128, 640]
    return [dense(f"fc{i}", batch, widths[i], widths[i + 1])
            for i in range(len(widths) - 1)]


def resnet8(batch: int = 1) -> list[Layer]:
    """MLPerf-tiny image classification ResNet8 on 32x32x3 CIFAR."""
    ls = [conv2d("stem", batch, 3, 16, 32, 32, 3, 3)]
    spec = [(16, 16, 32, 1), (16, 32, 16, 2), (32, 64, 8, 2)]
    for i, (cin, cout, res, stride) in enumerate(spec):
        ls.append(conv2d(f"b{i}.conv1", batch, cin, cout, res * stride,
                         res * stride, 3, 3, stride=stride))
        ls.append(conv2d(f"b{i}.conv2", batch, cout, cout, res, res, 3, 3))
        if stride != 1:
            ls.append(pointwise(f"b{i}.skip", batch, cin, cout, res, res))
    ls.append(dense("head", batch, 64, 10))
    return ls


def ds_cnn(batch: int = 1) -> list[Layer]:
    """MLPerf-tiny keyword spotting DS-CNN on 49x10 MFCC."""
    ls = [conv2d("stem", batch, 1, 64, 25, 5, 10, 4)]
    for i in range(4):
        ls.append(depthwise(f"dw{i}", batch, 64, 25, 5, 3, 3))
        ls.append(pointwise(f"pw{i}", batch, 64, 64, 25, 5))
    ls.append(dense("head", batch, 64, 12))
    return ls


def mobilenet_v1_025(batch: int = 1) -> list[Layer]:
    """MLPerf-tiny visual wake words MobileNetV1 x0.25 on 96x96x3."""
    ls = [conv2d("stem", batch, 3, 8, 96, 96, 3, 3, stride=2)]
    # (c_in, c_out, input_res, stride) for each dw/pw pair
    spec = [(8, 16, 48, 1), (16, 32, 48, 2), (32, 32, 24, 1),
            (32, 64, 24, 2), (64, 64, 12, 1), (64, 128, 12, 2),
            (128, 128, 6, 1), (128, 128, 6, 1), (128, 128, 6, 1),
            (128, 128, 6, 1), (128, 128, 6, 1), (128, 256, 6, 2),
            (256, 256, 3, 1)]
    for i, (cin, cout, res, stride) in enumerate(spec):
        ls.append(depthwise(f"dw{i}", batch, cin, res, res, 3, 3,
                            stride=stride))
        ls.append(pointwise(f"pw{i}", batch, cin, cout, res // stride,
                            res // stride))
    ls.append(dense("head", batch, 256, 2))
    return ls


TINYML_NETWORKS = {
    "deep_autoencoder": deep_autoencoder,
    "resnet8": resnet8,
    "ds_cnn": ds_cnn,
    "mobilenet_v1_025": mobilenet_v1_025,
}


# --------------------------------------------------------------------------- #
# Transformer-block lowering (beyond-paper: assigned LM architectures)         #
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class LMBlockSpec:
    """Minimal per-layer MVM description of a transformer-family block."""

    name: str
    d_model: int
    n_layers: int
    # (proj_name, in_features, out_features, calls_per_layer) tuples
    projections: tuple[tuple[str, int, int, int], ...]
    # MACs per token per layer spent in non-MVM compute (scans, attention
    # score/value products) — not IMC-mappable (DESIGN.md §5).
    non_mvm_macs_per_token: float = 0.0


#: serving phases of an LM request, in execution order.
SERVING_PHASES = ("prefill", "decode")


@dataclasses.dataclass(frozen=True)
class PhaseWorkload:
    """One layer group of one serving phase of one operating point,
    ready for the fused DSE.

    ``layers`` hold the MVM workloads of ONE superblock for ONE unit of
    the phase (the whole prompt for prefill, one decode step for
    decode); ``repeats`` scales the priced unit to the whole request
    batch's phase (the group's superblocks, times ``gen_len`` steps for
    decode) and is the only multiplier the sweep applies.  A phase
    whose layers do not all repeat alike -- a dense prologue, the MoE
    block, one routed-expert class -- is several consecutive entries of
    ``ServingPoint.phases`` with the same ``phase``, one per ``group``;
    a one-group phase leaves ``group`` empty.  The KV fields are
    whole-phase, whole-model byte volumes for the bytes-based cache
    hierarchy (``memory.KVCacheHierarchy``), held by the phase's first
    group (0 on the others):

    * ``kv_read_bytes`` / ``kv_write_bytes`` — cache traffic the phase
      generates (attention reads the live window per token, appends one
      slot per token; recurrent state is read + rewritten per step);
    * ``kv_live_bytes`` — peak live cache working set during the phase,
      which selects the hierarchy tier the traffic is priced at;
    * ``tokens_out`` — tokens this phase emits toward the serving
      throughput denominator (0 for prefill: prompt tokens are not
      generated output).
    """

    phase: str                       # "prefill" | "decode"
    layers: tuple[Layer, ...]        # one superblock, one phase unit
    repeats: float                   # units priced -> whole-request scale
    kv_read_bytes: float = 0.0
    kv_write_bytes: float = 0.0
    kv_live_bytes: float = 0.0
    tokens_out: float = 0.0
    group: str = ""                  # layer group within the phase

    def __post_init__(self) -> None:
        if self.phase not in SERVING_PHASES:
            raise ValueError(f"unknown serving phase {self.phase!r}; "
                             f"expected one of {SERVING_PHASES}")

    @property
    def tag(self) -> str:
        """``phase`` or ``phase/group``: names the group's network in a
        serving sweep."""
        return f"{self.phase}/{self.group}" if self.group else self.phase


@dataclasses.dataclass(frozen=True)
class ServingPoint:
    """One (prompt_len x batch) serving operating point: the phase-split
    workload bundle ``dse.sweep_serving`` prices as one lattice lane
    group.  Build from a model config with
    ``repro.core.lm_bridge.serving_points``."""

    name: str
    prompt_len: int
    batch: int
    gen_len: int
    phases: tuple[PhaseWorkload, ...]   # every group of every phase, in order

    @property
    def tokens_out(self) -> float:
        """Generated tokens per request batch (throughput denominator)."""
        return sum(p.tokens_out for p in self.phases)


def lm_block_workloads(spec: LMBlockSpec, tokens: int,
                       w_prec: int = 4, i_prec: int = 4) -> list[Layer]:
    """Lower an LM block into Dense workloads: one batched MVM per
    projection, B = tokens (the token dimension is the batch loop)."""
    layers = []
    for (pname, fin, fout, calls) in spec.projections:
        layers.append(dense(
            f"{spec.name}.{pname}", tokens * calls, fin, fout,
            w_prec=w_prec, i_prec=i_prec))
    return layers


def imc_coverage(spec: LMBlockSpec) -> float:
    """Fraction of per-token MACs that are IMC-mappable MVMs."""
    mvm = sum(fin * fout * calls for (_, fin, fout, calls) in spec.projections)
    total = mvm + spec.non_mvm_macs_per_token
    return mvm / total if total else 0.0


def total_macs(layers: Iterable[Layer]) -> int:
    return sum(l.macs for l in layers)
