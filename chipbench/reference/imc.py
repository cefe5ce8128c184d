"""Plain scalar reference of the IMC pricing model: the per-candidate loop.

A self-contained restatement of the paper's unified AIMC/DIMC energy
model (Eq. 1-11), the spatial-mapping enumeration, the weight- and
output-stationary dataflows, the outer-memory traffic pricing, the
KV-cache byte hierarchy and the LLM serving-point lowering.  It imports
nothing of the program under test: designs, layers and model shapes
come in as plain dicts made by the benchmark from its own data files.

Every float operation keeps the association of the program's scalar
oracle, so in float64 the reference agrees with a correct engine to the
last bit on an IEEE host; ``float32`` runs the same arithmetic one
precision lower (the control of ``chipbench.oracle``).

Units: energy fJ, capacitance fF, voltage V, frequency GHz, length nm.
"""

from __future__ import annotations

import math

import numpy as np

K1_ADC_FJ = 100.0
K2_ADC_FJ = 1e-3
K3_DAC_FJ = 44.0
CINV_SLOPE_FF_PER_NM = 0.01589
CINV_OFFSET_FF = 0.04616
GATE_CAP_FACTOR = 2.0
G_FA = 5.0
FCLK_DIMC_28NM_GHZ = 1.00
FCLK_AIMC_28NM_GHZ = 0.40
FCLK_NODE_EXPONENT = 0.8
FCLK_VDD_REF = 0.8
DEFAULT_ALPHA = 0.35
WRITE_CINV_FACTOR = 4.0
SRAM_CINV_FACTOR = 20.0
DRAM_FJ_PER_BIT = 4000.0
BUFFER_BYTES = 1 << 20
SRAM_KV_BYTES = 8 << 20
HBM_BYTES = 16 << 30
HBM_FJ_PER_BIT = 3500.0
FABRIC_FJ_PER_BIT = 10000.0
MAX_CANDIDATES = 4096

LOOP_DIMS = ("B", "G", "K", "C", "OX", "OY", "FX", "FY")
ROW_DIMS = ("C", "FX", "FY")
MACRO_DUP_DIMS = ("OX", "OY", "G")
MACRO_DIMS = MACRO_DUP_DIMS + ("K",)
SCHEDULES = ("ws", "os")
PRECISIONS = {"float64": float, "float32": np.float32}


# --------------------------------------------------------------------------- #
# layers                                                                       #
# --------------------------------------------------------------------------- #
def dim(layer: dict, d: str) -> int:
    return int(layer["dims"].get(d, 1))


def macs(layer: dict) -> int:
    return math.prod(dim(layer, d) for d in LOOP_DIMS)


def weight_elems(layer: dict) -> int:
    return math.prod(dim(layer, d) for d in ("G", "K", "C", "FX", "FY"))


def input_elems(layer: dict) -> int:
    ix = dim(layer, "OX") + dim(layer, "FX") - 1
    iy = dim(layer, "OY") + dim(layer, "FY") - 1
    return dim(layer, "B") * dim(layer, "G") * dim(layer, "C") * ix * iy


def output_elems(layer: dict) -> int:
    return math.prod(dim(layer, d) for d in ("B", "G", "K", "OX", "OY"))


def acc_depth(layer: dict) -> int:
    return dim(layer, "C") * dim(layer, "FX") * dim(layer, "FY")


def resident_bytes(layer: dict) -> int:
    return (weight_elems(layer) * layer["w_prec"]
            + input_elems(layer) * layer["i_prec"]
            + output_elems(layer) * layer["psum_prec"]) // 8


def shape_key(layer: dict) -> tuple:
    return (tuple(sorted((d, dim(layer, d)) for d in LOOP_DIMS)),
            layer["w_prec"], layer["i_prec"], layer["psum_prec"])


# --------------------------------------------------------------------------- #
# designs                                                                      #
# --------------------------------------------------------------------------- #
class Macro:
    """Derived quantities of one design dict, in one float precision."""

    def __init__(self, d: dict, F=float):
        self.F = F
        self.analog = bool(d["analog"])
        self.rows, self.cols = d["rows"], d["cols"]
        self.bw, self.bi = d["bw"], d["bi"]
        self.adc_res, self.dac_res = d["adc_res"], d["dac_res"]
        self.m_mux, self.n_macros = d["m_mux"], d["n_macros"]
        self.cols_per_adc, self.adc_share = d["cols_per_adc"], d["adc_share"]
        self.d1 = self.cols // self.bw
        self.d2 = self.rows // self.m_mux
        if self.analog:
            self.cc_bs = max(1, math.ceil(self.bi / self.dac_res))
        elif d["booth"]:
            self.cc_bs = max(1, math.ceil(self.bi / 2))
        else:
            self.cc_bs = self.bi
        self.tech_nm = F(d["tech_nm"])
        self.vdd = F(d["vdd"])
        self.c_inv = F(CINV_SLOPE_FF_PER_NM) * self.tech_nm + F(CINV_OFFSET_FF)
        self.c_gate = F(GATE_CAP_FACTOR) * self.c_inv
        base = FCLK_AIMC_28NM_GHZ if self.analog else FCLK_DIMC_28NM_GHZ
        self.f_clk_ghz = (F(base) * (F(28.0) / self.tech_nm)
                          ** F(FCLK_NODE_EXPONENT)
                          * (self.vdd / F(FCLK_VDD_REF)))
        self.sram_fj_per_bit = (F(SRAM_CINV_FACTOR) * self.c_inv
                                * self.vdd * self.vdd)

    def adc_fj(self) -> float:
        F = self.F
        return ((F(K1_ADC_FJ) * self.adc_res
                 + F(K2_ADC_FJ) * F(4.0) ** self.adc_res)
                * self.vdd * self.vdd)

    def dac_fj(self) -> float:
        return self.F(K3_DAC_FJ) * self.dac_res * self.vdd * self.vdd


def adder_tree_full_adders(n_inputs: int, b_in: int, F=float):
    if n_inputs <= 1:
        return F(0.0)
    n, b = F(n_inputs), F(b_in)
    return b * n + n - b - F(math.log2(n_inputs)) - F(1.0)


# --------------------------------------------------------------------------- #
# energy (paper Eq. 1-11) and one (layer, mapping, dataflow) cost              #
# --------------------------------------------------------------------------- #
def tile_energy(m: Macro, n_inputs: int, rows_used: int, cols_used: int,
                weight_loads: int, schedule: str) -> list:
    """[e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write, macs]."""
    F = m.F
    alpha = F(DEFAULT_ALPHA)
    v2 = m.vdd * m.vdd
    bw, bi, d1, d2, mux = m.bw, m.bi, m.d1, m.d2, m.m_mux
    tile_macs = F(n_inputs) * rows_used * cols_used
    rows_drv = min(rows_used, m.rows)
    words = min(cols_used, d1)
    mux_rows = math.ceil(rows_drv / mux)
    e_wl_line = m.c_inv * v2 * bw * d1
    e_bl_word = m.c_inv * v2 * bw * d2 * mux
    if m.analog:
        cc_prech = m.cc_bs * n_inputs
        e_wl = e_wl_line * rows_drv * cc_prech * alpha
        e_bl = e_bl_word * words * cc_prech * alpha
    elif mux > 1:
        cc_prech = mux * n_inputs
        e_wl = e_wl_line * mux_rows * cc_prech * alpha
        e_bl = e_bl_word * words * cc_prech * alpha
    else:
        cc_prech = weight_loads
        e_wl = e_wl_line * rows_drv * cc_prech * alpha
        e_bl = e_bl_word * words * cc_prech * alpha
    if m.analog:
        e_logic = F(0.0)
    else:
        g_mul = F(bw) * m.cc_bs / bi
        e_logic = v2 * m.c_gate * g_mul * tile_macs * alpha
    if m.analog:
        conversions = bw * (tile_macs / max(d2, 1))
        e_adc = m.adc_fj() * conversions / m.cols_per_adc
        f_tree = adder_tree_full_adders(max(2, bw), m.adc_res, F)
        cc_acc = m.cc_bs * n_inputs
        e_tree = m.c_gate * F(G_FA) * v2 * words * f_tree * cc_acc * alpha
    else:
        e_adc = F(0.0)
        f_tree = adder_tree_full_adders(d2, bw, F)
        occupancy = F(min(1.0, rows_drv / max(d2 * mux, 1)))
        cc_acc = m.cc_bs * mux * n_inputs
        e_tree = (m.c_gate * F(G_FA) * v2 * words * f_tree * occupancy
                  * cc_acc * alpha)
    if m.analog:
        e_dac = m.dac_fj() * rows_drv * (m.cc_bs * n_inputs)
    else:
        e_dac = F(0.0)
    if m.analog and schedule == "os":
        e_adc = e_adc + m.adc_fj() * words * weight_loads / m.cols_per_adc
        e_dac = e_dac + m.dac_fj() * rows_drv * weight_loads
    bits_written = weight_loads * rows_drv * words * bw
    e_write = F(WRITE_CINV_FACTOR) * m.c_inv * v2 * bits_written
    return [e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write, tile_macs]


def evaluate(layer: dict, m: Macro, mapping: dict, schedule: str) -> dict:
    """Energy (macro + outer-memory traffic) and cycles of one candidate.

    ``mapping`` is ``{"cols": {...}, "rows": {...}, "macros": {...}}``
    of unroll factors; ``schedule`` is ``"ws"`` or ``"os"``."""
    F = m.F
    cols, rows, mac = mapping["cols"], mapping["rows"], mapping["macros"]
    k_cols = cols.get("K", 1)
    k_macros = mac.get("K", 1)
    row_un = math.prod(rows.values()) if rows else 1
    dup_macros = math.prod(v for d, v in mac.items()
                           if d in MACRO_DUP_DIMS) or 1
    n_k_tiles = math.ceil(dim(layer, "K") / (k_cols * k_macros))
    n_acc_tiles = math.ceil(acc_depth(layer) / row_un)
    n_spatial_temporal = 1
    for d in MACRO_DUP_DIMS:
        n_spatial_temporal *= math.ceil(dim(layer, d) / mac.get(d, 1))
    weight_tiles = n_k_tiles * n_acc_tiles
    inputs_per_tile = dim(layer, "B") * n_spatial_temporal
    rows_used = min(row_un, acc_depth(layer))
    cols_used = min(k_cols, dim(layer, "K"))
    os_ = schedule == "os"
    weight_loads = inputs_per_tile if os_ else 1
    e = tile_energy(m, inputs_per_tile, rows_used, cols_used, weight_loads,
                    schedule)
    active = k_macros * dup_macros
    e = [(x * active) * weight_tiles for x in e]
    e_wl, e_bl, e_logic, e_adc, e_tree, e_dac, e_write, _ = e
    macro_fj = ((((e_wl + e_bl) + e_logic) + (e_adc + e_tree)) + e_dac) \
        + e_write
    cc_per_input = (m.cc_bs * m.adc_share if m.analog
                    else m.cc_bs * m.m_mux)
    cycles = (weight_tiles * inputs_per_tile * cc_per_input
              + rows_used * weight_tiles * weight_loads)
    weight_bits = (weight_elems(layer) * layer["w_prec"] * dup_macros
                   * (inputs_per_tile if os_ else 1))
    input_bits = (input_elems(layer) * layer["i_prec"]
                  * (1 if os_ else n_k_tiles))
    output_bits = output_elems(layer) * layer["psum_prec"]
    psum_bits = output_bits * (0 if os_ else 2 * max(0, n_acc_tiles - 1))
    per_bit = m.sram_fj_per_bit
    per_bit_w = (per_bit + F(DRAM_FJ_PER_BIT)
                 if resident_bytes(layer) > BUFFER_BYTES else per_bit)
    mem_fj = (((weight_bits * per_bit_w + input_bits * per_bit)
               + output_bits * per_bit) + psum_bits * per_bit)
    return {"energy_fj": macro_fj + mem_fj, "cycles": cycles}


def objective_value(cost: dict, objective: str):
    if objective == "energy":
        return cost["energy_fj"]
    if objective == "latency":
        return cost["cycles"]
    if objective == "edp":
        return cost["energy_fj"] * cost["cycles"]
    raise KeyError(objective)


# --------------------------------------------------------------------------- #
# mapping enumeration                                                          #
# --------------------------------------------------------------------------- #
def _unroll_candidates(dim_size: int, cap: int) -> list[int]:
    cap = max(1, min(dim_size, cap))
    cands = {1, cap}
    p = 2
    while p < cap:
        cands.add(p)
        p *= 2
    if dim_size <= cap:
        cands.add(dim_size)
    return sorted(cands)


def is_legal(layer: dict, m: Macro, mapping: dict) -> bool:
    cols, rows, mac = mapping["cols"], mapping["rows"], mapping["macros"]
    if (math.prod(cols.values()) > m.d1
            or math.prod(rows.values()) > m.rows
            or math.prod(mac.values()) > m.n_macros):
        return False
    for dims, allowed in ((cols, ("K",)), (rows, ROW_DIMS),
                          (mac, MACRO_DIMS)):
        if any(d not in allowed or u < 1 for d, u in dims.items()):
            return False
    for d in set(cols) | set(rows) | set(mac):
        if cols.get(d, 1) * rows.get(d, 1) * mac.get(d, 1) > dim(layer, d):
            return False
    return True


def enumerate_mappings(layer: dict, m: Macro):
    """Legal spatial mappings in the fixed enumeration order (which
    decides ties: the first minimum wins)."""
    k = dim(layer, "K")
    count = 0
    for k_col in _unroll_candidates(k, m.d1):
        row_opts = []
        for c_un in _unroll_candidates(dim(layer, "C"), m.rows):
            rem = m.rows // c_un
            for fx_un in _unroll_candidates(dim(layer, "FX"), rem):
                for fy_un in _unroll_candidates(dim(layer, "FY"),
                                                rem // fx_un):
                    row_opts.append({"C": c_un, "FX": fx_un, "FY": fy_un})
        for rows in row_opts:
            macro_opts = [{}]
            if m.n_macros > 1:
                for d in MACRO_DUP_DIMS:
                    macro_opts += [{d: u} for u in _unroll_candidates(
                        dim(layer, d), m.n_macros) if u > 1]
                macro_opts += [{"K": u} for u in _unroll_candidates(
                    max(1, k // k_col), m.n_macros) if u > 1]
            for mac in macro_opts:
                mp = {"cols": {"K": k_col}, "rows": dict(rows),
                      "macros": mac}
                if is_legal(layer, m, mp):
                    yield mp
                    count += 1
                    if count >= MAX_CANDIDATES:
                        return


def best_mapping(layer: dict, m: Macro, objective: str = "energy",
                 schedules=SCHEDULES) -> dict:
    """The (mapping, dataflow) minimum of ``objective``, mapping outer,
    dataflow inner, first minimum kept."""
    best = None
    best_obj = None
    for mp in enumerate_mappings(layer, m):
        for s in schedules:
            cost = evaluate(layer, m, mp, s)
            obj = objective_value(cost, objective)
            if best is None or obj < best_obj:
                best, best_obj = dict(cost, mapping=mp, schedule=s), obj
    if best is None:
        raise ValueError(f"no legal mapping for {layer['name']}")
    return best


def network(layers, m: Macro, objective: str = "energy",
            schedules=SCHEDULES, memo: dict | None = None) -> dict:
    """Per-layer winners and network totals over the eligible layers
    (energy folded left to right in layer order, cycles summed)."""
    memo = {} if memo is None else memo
    winners = []
    energy = 0
    cycles = 0
    for layer in layers:
        if not layer.get("imc_eligible", True):
            continue
        key = (shape_key(layer), objective)
        if key not in memo:
            memo[key] = best_mapping(layer, m, objective, schedules)
        w = memo[key]
        winners.append(w)
        energy = energy + w["energy_fj"]
        cycles = cycles + w["cycles"]
    return {"layers": winners, "energy_fj": energy, "cycles": cycles}


# --------------------------------------------------------------------------- #
# LLM serving points (attention + gated-FFN decoder, no sliding window)        #
# --------------------------------------------------------------------------- #
def lm_layers(model: dict, tokens: int, phase: str, w_prec: int = 4,
              i_prec: int = 4) -> list[dict]:
    """One decoder layer's projections as dense (B=tokens, K=out, C=in)
    workloads, in the order wq, wk, wv, wo, ffn up, down, gate."""
    d = model["hidden_size"]
    q = model["num_attention_heads"] * model["kv_channels"]
    kv = model["multi_query_group_num"] * model["kv_channels"]
    ff = model["ffn_hidden_size"]
    projs = [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
             ("ffn_up", d, ff), ("ffn_down", ff, d), ("ffn_gate", d, ff)]
    return [{"name": f"{phase}.p0.{n}", "dims": {"B": tokens, "K": fo,
                                                  "C": fi},
             "w_prec": w_prec, "i_prec": i_prec, "psum_prec": 24}
            for n, fi, fo in projs]


def _span_sum(lo: int, hi: int) -> float:
    """sum of t for t = lo..hi (full causal attention, no window)."""
    if hi < lo:
        return 0.0
    return (hi * (hi + 1) - (lo - 1) * lo) / 2.0


def serving_point(model: dict, prompt_len: int, batch: int,
                  gen_len: int, kv_itemsize: int = 2) -> dict:
    """Both phases of one (prompt, batch, gen) point with their KV-cache
    byte volumes (whole model, whole phase)."""
    n = model["num_layers"]
    slot = 2.0 * model["multi_query_group_num"] * model["kv_channels"] \
        * kv_itemsize
    ctx = prompt_len + gen_len
    pre_reads = slot * (1.0 * _span_sum(1, prompt_len)
                        + 0.0 * _span_sum(1, prompt_len))
    dec_reads = slot * (1.0 * _span_sum(prompt_len, ctx - 1)
                        + 0.0 * _span_sum(prompt_len, ctx - 1))
    pre_writes = slot * prompt_len + 0.0
    dec_writes = slot * gen_len + 0.0 * gen_len

    def live(c: int) -> float:
        return batch * n * ((1.0 * c + 0.0 * c) * slot + 0.0)

    return {"name": f"{model['name']}/p{prompt_len}xb{batch}",
            "tokens_out": 0.0 + float(batch) * gen_len,
            "phases": [
                {"phase": "prefill",
                 "layers": lm_layers(model, batch * prompt_len, "prefill"),
                 "repeats": float(n),
                 "kv_read": batch * n * pre_reads,
                 "kv_write": batch * n * pre_writes,
                 "kv_live": live(prompt_len)},
                {"phase": "decode",
                 "layers": lm_layers(model, batch, "decode"),
                 "repeats": float(n) * gen_len,
                 "kv_read": batch * n * (dec_reads + 0.0 * gen_len),
                 "kv_write": batch * n * dec_writes,
                 "kv_live": live(ctx)}]}


def kv_energy(m: Macro, read: float, write: float, live: float):
    F = m.F
    per_bit = m.sram_fj_per_bit
    if live <= SRAM_KV_BYTES:
        rate = per_bit
    elif live <= HBM_BYTES:
        rate = per_bit + F(HBM_FJ_PER_BIT)
    else:
        rate = per_bit + F(HBM_FJ_PER_BIT + FABRIC_FJ_PER_BIT)
    return (F(read) + F(write)) * F(8.0) * rate


def serve(point: dict, m: Macro, objective: str = "energy",
          schedules=SCHEDULES, memo: dict | None = None) -> dict:
    """Serving cost of one point on one design: MVM energy and cycles
    per phase (times its repeats), KV traffic, rate and J/token."""
    F = m.F
    energy = F(0.0)
    kv = F(0.0)
    cycles = F(0.0)
    phases = []
    for ph in point["phases"]:
        net = network(ph["layers"], m, objective, schedules, memo)
        phases.append(net)
        energy = energy + net["energy_fj"] * F(ph["repeats"])
        cycles = cycles + F(net["cycles"]) * F(ph["repeats"])
        kv = kv + kv_energy(m, ph["kv_read"], ph["kv_write"], ph["kv_live"])
    total = energy + kv
    time_s = cycles / (m.f_clk_ghz * F(1e9))
    tokens = F(point["tokens_out"])
    return {"energy_fj": energy, "kv_energy_fj": kv, "cycles": cycles,
            "tokens_per_s": tokens / time_s,
            "j_per_token": (total * F(1e-15)) / tokens, "phases": phases}
