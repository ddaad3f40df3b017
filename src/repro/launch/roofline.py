"""Roofline-term derivation from the compiled dry-run artifact.

    compute    = HLO_FLOPs / (chips * peak_FLOPs)
    memory     = HLO_bytes / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()``; collective
bytes are parsed out of the (post-SPMD) HLO text by summing the result-shape
bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op.

Peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``.  The
dry-run model targets a TPU v5e (:data:`DRYRUN_KIND`); a measured run is
compared against the peaks of the device it ran on, and a device with no
published entry is an error, never a default.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, NamedTuple, Optional


class Peak(NamedTuple):
    """Published per-chip peaks of one device kind."""

    flops: float      # FLOP/s, bf16 (the MXU's native rate)
    hbm_bw: float     # bytes/s
    link_bw: float    # bytes/s per ICI link
    source: str


# Keyed by jax Device.device_kind.  Only published numbers go here.
PEAKS: Dict[str, Peak] = {
    "TPU v5 lite": Peak(
        flops=197e12, hbm_bw=819e9, link_bw=50e9,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
               '819 GB/s HBM, 1,600 Gbit/s ICI per chip (link_bw: that '
               'total over the chip\'s 4 ICI links)'),
}

DRYRUN_KIND = "TPU v5 lite"   # the chip the LM dry-run model assumes


def peak_for(device_kind: str) -> Peak:
    """Published peaks of ``device_kind``; raises for an unknown kind."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}; add it to "
            "repro.launch.roofline.PEAKS with its source"
        ) from None

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)

# e.g.  %all-reduce.5 = f32[16,128]{1,0} all-reduce(...)
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*((?:[a-z0-9_]+\[[^\]]*\][^ ]*\s*,?\s*)+)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# ----------------------------------------------------------------------------
# Trip-count-aware HLO cost analysis
#
# XLA's HloCostAnalysis counts while-loop bodies ONCE (verified empirically:
# a scan of 10 matmuls reports the flops of 1).  Every scanned structure —
# layer stacks, q-chunked attention, SSD sequence chunks — is therefore
# undercounted by its trip count.  This analyzer walks the HLO text, builds
# the computation call graph, reads each while op's
# backend_config known_trip_count, and multiplies costs accordingly.
# ----------------------------------------------------------------------------

_COMP_HDR_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_REFS_RE = re.compile(
    r"(?:calls=|condition=|body=|branch_computations=\{|to_apply=)"
    r"([%\w.\-, ]+)"
)
# type part matched lazily: tuple types contain commas, braces and
# /*index=N*/ comments; the first bare `word(` after it is the opcode.
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*?)\s([a-z][\w\-]*)\(")
_CONST_INT_RE = re.compile(r"=\s*s32\[\]\s*constant\((\d+)\)")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def _parse_shape(type_str: str):
    """'f32[256,128]{1,0}' -> (dtype, [256,128]); tuples -> list of both."""
    shapes = []
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        dims_l = [int(d) for d in dims.split(",") if d] if dims else []
        shapes.append((dt, dims_l))
    return shapes


def _shape_list_bytes(shapes) -> int:
    total = 0
    for dt, dims in shapes:
        n = 1
        for d in dims:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _split_computations(hlo_text: str):
    """name -> list of op lines (flat text split, brace-delimited)."""
    comps = {}
    cur_name, cur_lines = None, []
    entry = None
    for line in hlo_text.splitlines():
        if cur_name is None:
            s = line.strip()
            m = _COMP_HDR_RE.match(s)
            if m and s.endswith("{") and "->" in s:
                cur_name = m.group(1)
                if line.lstrip().startswith("ENTRY"):
                    entry = cur_name
                cur_lines = []
        else:
            if line.strip() == "}":
                comps[cur_name] = cur_lines
                cur_name = None
            else:
                cur_lines.append(line)
    return comps, entry


class _CompCost:
    __slots__ = ("flops", "bytes", "coll", "calls")

    def __init__(self):
        self.flops = 0.0
        self.bytes = 0.0
        self.coll = {k: 0.0 for k in _COLLECTIVES}
        self.calls = []   # (callee_name, multiplier, kind)


def _analyze_computation(lines, fusion_flops: Dict[str, float],
                         trip_guess: Optional[Dict[str, int]] = None,
                         fusion_io: Optional[Dict[str, float]] = None):
    """One pass over a computation's ops.

    Returns a _CompCost where `bytes` counts operand+result bytes of ops at
    this level (fusion internals excluded — the fusion boundary is what
    touches HBM), `flops` counts dot flops at this level plus the dot flops
    of any kLoop/kOutput fusion bodies it calls, and `calls` lists control-
    flow edges (while/conditional/call) with multipliers.
    """
    cost = _CompCost()
    trip_guess = trip_guess or {}
    fusion_io = fusion_io or {}
    shapes = {}   # op name -> result type string

    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.group(1), m.group(2), m.group(3)
        shapes[name] = type_str

        if opcode in ("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast", "after-all", "copy", "copy-start",
                      "copy-done"):
            # copies of while carries are elided by buffer aliasing on real
            # hardware; counting them would charge the full KV cache / param
            # stack per scan iteration
            continue

        result_shapes = _parse_shape(type_str)
        result_bytes = _shape_list_bytes(result_shapes)

        # operand bytes from the symbol table (parameters included)
        paren = line[line.find(opcode + "(") + len(opcode) + 1:]
        depth = 1
        arglist = []
        for ch in paren:
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
            arglist.append(ch)
        operand_names = _OPERAND_RE.findall("".join(arglist))
        operand_bytes = sum(
            _shape_list_bytes(_parse_shape(shapes.get(o, "")))
            for o in operand_names
        )

        if opcode in ("dynamic-slice", "gather"):
            # in-place view semantics: traffic = the slice read + written,
            # not the full source tensor XLA's model charges
            cost.bytes += 2 * result_bytes
            continue
        if opcode in ("dynamic-update-slice", "scatter"):
            # traffic = the update slice (operand 1) read + written
            upd = (operand_names[1]
                   if len(operand_names) > 1 else None)
            upd_bytes = _shape_list_bytes(_parse_shape(shapes.get(upd, "")))
            cost.bytes += 2 * (upd_bytes or result_bytes)
            continue

        base_kind = opcode.replace("-start", "").replace("-done", "")
        if base_kind in _COLLECTIVES:
            if not opcode.endswith("-done"):
                cost.coll[base_kind] += result_bytes
            cost.bytes += result_bytes + operand_bytes
            continue

        if opcode == "while":
            trips = None
            t = _TRIP_RE.search(line)
            if t:
                trips = int(t.group(1))
            refs = re.search(r"body=%?([\w.\-]+)", line)
            cond = re.search(r"condition=%?([\w.\-]+)", line)
            if trips is None and cond is not None:
                # fall back to the loop bound in the condition computation
                # (the s32 constant compared against the induction counter)
                trips = trip_guess.get(cond.group(1))
            if trips is None:
                trips = 1
            if refs:
                cost.calls.append((refs.group(1), trips, "while"))
            if cond:
                cost.calls.append((cond.group(1), trips + 1, "while"))
            continue

        if opcode == "conditional":
            for grp in re.findall(r"(?:branch_computations=\{([^}]*)\}|"
                                  r"true_computation=%?([\w.\-]+)|"
                                  r"false_computation=%?([\w.\-]+))", line):
                for g in grp:
                    if not g:
                        continue
                    for ref in g.split(","):
                        ref = ref.strip().lstrip("%")
                        if ref:
                            cost.calls.append((ref, 1, "cond"))
            continue

        if opcode in ("call", "async-start"):
            r = re.search(r"to_apply=%?([\w.\-]+)", line)
            if r:
                cost.calls.append((r.group(1), 1, "call"))
            cost.bytes += result_bytes + operand_bytes
            continue

        if opcode == "fusion":
            r = re.search(r"calls=%?([\w.\-]+)", line)
            if r:
                cost.flops += fusion_flops.get(r.group(1), 0.0)
                cost.bytes += fusion_io.get(
                    r.group(1), result_bytes + operand_bytes)
            else:
                cost.bytes += result_bytes + operand_bytes
            continue

        if opcode == "dot":
            # flops = 2 * prod(result dims) * prod(contracting dims of LHS)
            lhs = operand_names[0] if operand_names else None
            lhs_shapes = _parse_shape(shapes.get(lhs, ""))
            k = 1
            cm = _CONTRACT_RE.search(line)
            if cm and lhs_shapes:
                dims = lhs_shapes[0][1]
                for ci in cm.group(1).split(","):
                    if ci and int(ci) < len(dims):
                        k *= dims[int(ci)]
            out_elems = 1
            for dt, ds in result_shapes:
                for d in ds:
                    out_elems *= d
            cost.flops += 2.0 * out_elems * k
            cost.bytes += result_bytes + operand_bytes
            continue

        if opcode == "convolution":
            # flops = 2 * out_elems * prod(window dims). Exact for the
            # depthwise convs these models use (mamba/RG-LRU conv1d and
            # their transposed gradients); dense multi-channel convs would
            # need an extra C_in/groups factor, but none appear here.
            win = re.search(r"window=\{size=([0-9x]+)", line)
            wprod = 1
            if win:
                for d in win.group(1).split("x"):
                    wprod *= int(d)
            out_elems = 1
            for dt, ds in result_shapes:
                for d in ds:
                    out_elems *= d
            cost.flops += 2.0 * out_elems * wprod
            cost.bytes += result_bytes + operand_bytes
            continue

        # every other op: memory traffic only (elementwise flops are noise
        # next to matmuls at these shapes)
        cost.bytes += result_bytes + operand_bytes

    return cost


def _dot_flops_only(lines):
    """Dot/conv flops of a fusion body (no bytes — internals stay on-chip)."""
    return _analyze_computation(lines, {}).flops


def _fusion_io_bytes(lines) -> float:
    """HBM traffic estimate of one fusion: bytes actually read from each
    operand + the result write.

    A fusion that internally dynamic-slices/gathers a parameter (the layer's
    slice of a stacked param / KV tensor) only reads the slice, not the full
    operand XLA's boundary model charges.
    """
    shapes = {}
    params = {}
    alias = {}    # view ops resolve to their root param
    sliced = set()
    dus_results = set()
    slice_bytes = 0.0
    root_bytes = 0.0
    root_name = None
    compute_ops = 0

    def root_of(n):
        seen = set()
        while n in alias and n not in seen:
            seen.add(n)
            n = alias[n]
        return n

    for line in lines:
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, type_str, opcode = m.group(1), m.group(2), m.group(3)
        shapes[name] = type_str
        rb = _shape_list_bytes(_parse_shape(type_str))
        ops = _OPERAND_RE.findall(line[line.find(opcode + "(")::])
        if opcode == "parameter":
            params[name] = rb
        elif opcode in ("bitcast", "copy", "reshape", "transpose",
                        "broadcast", "convert"):
            if ops:
                alias[name] = ops[0]
        elif opcode in ("dynamic-slice", "gather", "slice"):
            src = root_of(ops[0]) if ops else None
            if src in params:
                sliced.add(src)
                slice_bytes += rb
            compute_ops += 1
        elif opcode in ("dynamic-update-slice", "scatter"):
            # in-place update of (a view of) a parameter: traffic is the
            # update slice read + written, not the whole destination
            src = root_of(ops[0]) if ops else None
            upd = ops[1] if len(ops) > 1 else None
            upd_bytes = _shape_list_bytes(_parse_shape(shapes.get(upd, "")))
            if src in params:
                sliced.add(src)
                slice_bytes += 2 * (upd_bytes or rb)
                dus_results.add(name)
            compute_ops += 1
        elif opcode not in ("constant", "get-tuple-element", "tuple"):
            compute_ops += 1
        if line.lstrip().startswith("ROOT"):
            root_bytes = rb
            root_name = name

    if compute_ops == 0:
        # pure dtype/layout-change fusion (e.g. the wholesale bf16->f32
        # cache upcast the CPU backend hoists out of while loops for its
        # f32-only matmuls) — does not exist on TPU, where the MXU consumes
        # bf16 natively and layout changes fuse into consumers.
        return 0.0
    if root_name is not None and root_of(root_name) in dus_results:
        # output aliases the in-place-updated input buffer
        root_bytes = 0.0
    read = slice_bytes + sum(
        b for n, b in params.items() if n not in sliced
    )
    return read + root_bytes


def analyze_hlo(hlo_text: str) -> Dict[str, float]:
    """Trip-count-aware totals over the whole module.

    Returns {"flops", "bytes_accessed", "collective_bytes", per-kind...}.
    """
    comps, entry = _split_computations(hlo_text)
    if entry is None:
        return {"flops": 0.0, "bytes_accessed": 0.0, "collective_bytes": 0.0}

    # loop-bound constants per computation (while-condition fallback):
    # only constants that feed the ROOT compare count — an unrelated
    # constant elsewhere in the condition must not become the trip count
    trip_guess: Dict[str, int] = {}
    for name, lines in comps.items():
        const_vals: Dict[str, int] = {}
        root_ops: list = []
        for ln in lines:
            m = _CONST_INT_RE.search(ln)
            d = _DEF_RE.match(ln)
            if m and d:
                const_vals[d.group(1)] = int(m.group(1))
            if ln.lstrip().startswith("ROOT") and d:
                paren = ln[ln.find(d.group(3) + "(") + len(d.group(3)) + 1:]
                root_ops = _OPERAND_RE.findall(paren.split("), ")[0])
        feeding = [const_vals[o] for o in root_ops if o in const_vals]
        if feeding:
            trip_guess[name] = max(feeding)
        elif const_vals:
            trip_guess[name] = max(const_vals.values())

    # fusion bodies first (flops attributed at the fusion call site)
    fusion_flops = {name: _dot_flops_only(lines)
                    for name, lines in comps.items()}
    fusion_io = {name: _fusion_io_bytes(lines)
                 for name, lines in comps.items()}
    costs = {name: _analyze_computation(lines, fusion_flops, trip_guess,
                                        fusion_io)
             for name, lines in comps.items()}

    # propagate multipliers from ENTRY through the control-flow call graph
    mult: Dict[str, float] = {}

    def visit(name, m):
        if name not in costs:
            return
        mult[name] = mult.get(name, 0.0) + m
        for callee, k, kind in costs[name].calls:
            visit(callee, m * k)

    visit(entry, 1.0)

    total_flops = 0.0
    total_bytes = 0.0
    coll = {k: 0.0 for k in _COLLECTIVES}
    for name, m in mult.items():
        c = costs[name]
        total_flops += m * c.flops
        total_bytes += m * c.bytes
        for k in _COLLECTIVES:
            coll[k] += m * c.coll[k]

    out = {"flops": total_flops, "bytes_accessed": total_bytes,
           "collective_bytes": float(sum(coll.values()))}
    out.update({f"coll_{k}": v for k, v in coll.items()})
    return out


def parse_collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind over the HLO module text.

    ``-start`` ops are counted, matching ``-done`` duplicates are not.
    """
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue  # avoid double counting async pairs
        m = _OP_RE.search(line)
        if not m:
            continue
        shapes, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shapes)
    return out


@dataclasses.dataclass
class Roofline:
    flops: float
    bytes_accessed: float
    collective_bytes: float
    chips: int
    model_flops: Optional[float] = None
    peak: Peak = PEAKS[DRYRUN_KIND]

    @property
    def t_compute(self) -> float:
        return self.flops / (self.chips * self.peak.flops)

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / (self.chips * self.peak.hbm_bw)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes / (self.chips * self.peak.link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound time — how close the dominant term
        lets us get to the compute roofline."""
        if self.model_flops is None:
            return float("nan")
        t_useful = self.model_flops / (self.chips * self.peak.flops)
        t_bound = max(self.t_compute, self.t_memory, self.t_collective)
        return t_useful / t_bound if t_bound > 0 else float("nan")

    def as_dict(self) -> dict:
        return {
            "flops": self.flops,
            "bytes_accessed": self.bytes_accessed,
            "collective_bytes": self.collective_bytes,
            "chips": self.chips,
            "model_flops": self.model_flops,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "roofline_fraction": self.roofline_fraction,
            "useful_flops_ratio": (
                self.model_flops / self.flops
                if self.model_flops and self.flops else None
            ),
        }


def achieved_vs_peak(flops: float, bytes_accessed: float, measured_s: float,
                     device=None, chips: int = 1,
                     collective_bytes: float = 0.0) -> dict:
    """Measured-wall-clock term next to the roofline model.

    Given a *measured* kernel wall-clock from the :mod:`repro.obs.timing`
    harness (jit-warm + ``block_until_ready``) and the kernel's model
    flops / HBM bytes, report the achieved rates as fractions of the peaks
    of ``device`` (default ``jax.devices()[0]``) and of the roofline bound
    itself.  ``achieved_vs_model`` is ``t_bound / measured`` — 1.0 means
    the kernel runs exactly at its modeled roofline.

    Off TPU the shares are None ("not measured"): a CPU or interpret-mode
    time is no fraction of any chip's peak.  A TPU whose ``device_kind``
    has no :data:`PEAKS` entry raises.
    """
    if measured_s <= 0:
        raise ValueError(f"measured_s must be positive, got {measured_s}")
    if device is None:
        import jax

        device = jax.devices()[0]
    out = {
        "measured_s": measured_s,
        "achieved_flops_per_s": flops / measured_s,
        "achieved_bytes_per_s": bytes_accessed / measured_s,
        "device_kind": device.device_kind,
        "frac_peak_compute": None,
        "frac_peak_memory": None,
        "model_t_compute_s": None,
        "model_t_memory_s": None,
        "model_bottleneck": None,
        "achieved_vs_model": None,
    }
    if device.platform != "tpu":
        return out
    peak = peak_for(device.device_kind)
    model = Roofline(flops=flops, bytes_accessed=bytes_accessed,
                     collective_bytes=collective_bytes, chips=chips,
                     peak=peak)
    t_bound = max(model.t_compute, model.t_memory, model.t_collective)
    out.update({
        "frac_peak_compute": (flops / measured_s) / (chips * peak.flops),
        "frac_peak_memory": (bytes_accessed / measured_s)
                            / (chips * peak.hbm_bw),
        "model_t_compute_s": model.t_compute,
        "model_t_memory_s": model.t_memory,
        "model_bottleneck": model.bottleneck,
        "achieved_vs_model": (t_bound / measured_s) if t_bound > 0 else None,
    })
    return out


def count_params(param_structs) -> int:
    import jax
    import numpy as np

    return int(sum(np.prod(l.shape) for l in jax.tree.leaves(param_structs)))


def active_params(cfg, param_structs) -> int:
    """6*N*D uses N_active for MoE (top_k of n_experts expert params)."""
    import jax
    import numpy as np

    total = count_params(param_structs)
    if cfg is None or getattr(cfg, "moe", None) is None:
        return total
    # expert weights: (E, D, F) x3 per layer
    E, k = cfg.moe.n_experts, cfg.moe.top_k
    expert = 3 * cfg.n_layers * E * cfg.d_model * cfg.d_ff
    return total - expert + int(expert * k / E)


def model_flops(cfg, param_structs, shape_kind: str, tokens: int) -> float:
    """6*N*D for training, 2*N*D for inference (per step)."""
    n = active_params(cfg, param_structs)
    mult = 6.0 if shape_kind == "train" else 2.0
    return mult * n * tokens
