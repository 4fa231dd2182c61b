"""Roofline model from compiled dry-run artifacts, with per-device peaks.

Peaks come from :data:`PEAKS`, one entry per ``jax.Device.device_kind``
with its published source; a device kind missing from the table is an
error, never a default. The dry run and the decode predictor target the
TPU v5e (``"TPU v5 lite"``).

Sources:
  * ``compiled.cost_analysis()`` -> HLO_FLOPs, HLO_bytes. On this backend the
    numbers are per-device (the SPMD-partitioned module), verified against a
    hand-computed matmul in tests.
  * collective bytes are NOT in cost_analysis: we parse the post-SPMD HLO
    text and sum, per collective op, the bytes each device moves over ICI
    using standard ring-algorithm factors:
        all-gather:        out_local * (n-1)/n      (receives the other shards)
        reduce-scatter:    in_local  * (n-1)/n
        all-reduce:        2 * in_local * (n-1)/n   (RS + AG)
        all-to-all:        in_local  * (n-1)/n
        collective-permute: in_local                (one hop send)
    with n = participants per replica group, parsed from replica_groups.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Any


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""

    bf16_flops: float  # FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s of chip-to-chip interconnect, all links
    ici_links: int
    source: str

    @property
    def ici_link_bw(self) -> float:
        """Bytes/s of one link: the collective term models one serialized
        link (conservative)."""
        return self.ici_bw / self.ici_links


V5E = "TPU v5 lite"

# Keyed by ``jax.Device.device_kind``.
PEAKS: dict[str, DevicePeaks] = {
    V5E: DevicePeaks(
        bf16_flops=197e12,
        hbm_bw=819e9,
        ici_bw=1600e9 / 8,  # 1,600 Gbit/s
        ici_links=4,  # assumed split: 50 GB/s per link
        source=(
            'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
            "393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s "
            "chip-to-chip interconnect"
        ),
    ),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

# e.g.  bf16[16,4096,128]{2,1,0}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def _result_bytes(line: str) -> int:
    """Bytes of the op result (first shape(s) on the line, incl. tuples)."""
    head = line.split(" = ", 1)
    if len(head) != 2:
        return 0
    lhs_types = head[1]
    # result type is everything before the op name; find the op name position
    m = re.search(r"\)? *(" + "|".join(_COLLECTIVES) + r")", lhs_types)
    region = lhs_types[: m.start()] if m else lhs_types
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(region))


def _operand_bytes(line: str) -> int:
    """Bytes of operands (shapes inside the call parens)."""
    m = re.search(r"(" + "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(", line)
    if not m:
        return 0
    args = line[m.end() :]
    depth = 1
    out = []
    for ch in args:
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                break
        out.append(ch)
    return sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall("".join(out)))


def _group_size(line: str, total_devices: int) -> int:
    # iota form: replica_groups=[8,32]<=[...] -> groups of 32
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=", line)
    if m:
        return int(m.group(2))
    # explicit form: replica_groups={{0,1,2,3},{...}} -> first group size
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\{\}", line)
    if m:
        return total_devices
    return total_devices


@dataclass
class CollectiveStats:
    per_device_bytes: float = 0.0
    op_bytes: dict[str, float] = field(default_factory=dict)
    op_counts: dict[str, int] = field(default_factory=dict)
    unattributed_comps: int = 0


# --------------------------------------------------------------------------
# Loop-aware parsing: scan bodies appear once in the HLO text but execute
# trip-count times. We reconstruct computations, while-op edges, and trip
# counts (the s32 constant in the loop condition), then weight each
# computation's collectives by the product of enclosing trip counts.
# --------------------------------------------------------------------------
_WHILE_RE = re.compile(r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CALL_RE = re.compile(r"(?:to_apply|calls|body|branch_computations)=\{?%?([\w.\-]+(?:, *%?[\w.\-]+)*)\}?")
_S32_CONST_RE = re.compile(r"s32\[\] constant\((\d+)\)")
_COMMENT_RE = re.compile(r"/\*.*?\*/")


def _split_computations(hlo_text: str) -> tuple[dict[str, list[str]], str | None]:
    """Computation name -> body lines. Headers are column-0 lines ending in
    '{'; the name is the token before the first '(' (names may contain dots,
    dashes, 'wide.' prefixes and nested-paren arg lists, so no full-line
    regex — just the prefix token)."""
    comps: dict[str, list[str]] = {}
    entry = None
    cur: str | None = None
    for line in hlo_text.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{") and "(" in line:
            name = line.split("(")[0].strip()
            is_entry = name.startswith("ENTRY")
            name = name.replace("ENTRY", "").strip().lstrip("%").strip()
            if not name:
                cur = None
                continue
            cur = name
            comps[cur] = []
            if is_entry:
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(_COMMENT_RE.sub("", line.strip()))
    return comps, entry


def _trip_count(cond_lines: list[str]) -> int:
    consts = [int(m.group(1)) for l in cond_lines for m in _S32_CONST_RE.finditer(l)]
    return max(consts) if consts else 1


def _comp_multipliers(comps: dict[str, list[str]], entry: str | None) -> dict[str, float]:
    mult: dict[str, float] = {}
    if entry is None:
        return {name: 1.0 for name in comps}

    def visit(name: str, m: float) -> None:
        if name not in comps:
            return
        mult[name] = mult.get(name, 0.0) + m
        for line in comps[name]:
            wm = _WHILE_RE.search(line)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                visit(body, m * _trip_count(comps.get(cond, [])))
                continue
            cm = _CALL_RE.search(line)
            if cm and "while(" not in line:
                for callee in re.split(r", *%?", cm.group(1)):
                    visit(callee, m)

    visit(entry, 1.0)
    return mult


def parse_collectives(hlo_text: str, total_devices: int) -> CollectiveStats:
    comps, entry = _split_computations(hlo_text)
    if not comps:
        comps, entry = {"__all__": [l.strip() for l in hlo_text.splitlines()]}, "__all__"
    mult = _comp_multipliers(comps, entry)
    stats = CollectiveStats()
    for name, lines in comps.items():
        weight = mult.get(name)
        if weight is None:
            # Unreached in the call graph (parser gap): count once rather
            # than zero, and flag it.
            if any(f" {c}(" in l or f"{c}-start(" in l for l in lines for c in _COLLECTIVES):
                stats.unattributed_comps += 1
                weight = 1.0
            else:
                continue
        if weight == 0.0:
            continue
        _accumulate(lines, total_devices, stats, weight)
    return stats


def _accumulate(
    lines: list[str], total_devices: int, stats: CollectiveStats, weight: float
) -> None:
    for stripped in lines:
        op = next(
            (
                c
                for c in _COLLECTIVES
                if f" {c}(" in stripped or f"{c}-start(" in stripped
            ),
            None,
        )
        if op is None:
            continue
        if f"{op}-done" in stripped:
            continue  # paired with -start; don't double count
        n = _group_size(stripped, total_devices)
        if n <= 1:
            continue
        # Post-SPMD HLO body lines carry only RESULT shapes (operands are
        # bare refs), so byte costs derive from the result + op semantics.
        r = _result_bytes(stripped)
        if op == "all-gather":
            moved = r * (n - 1) / n  # result = gathered; each device receives the rest
        elif op == "reduce-scatter":
            moved = r * (n - 1)  # operand = result * n; ring cost = operand*(n-1)/n
        elif op == "all-reduce":
            moved = 2.0 * r * (n - 1) / n  # operand == result; RS + AG
        elif op == "all-to-all":
            moved = r * (n - 1) / n  # operand size == result size
        else:  # collective-permute
            moved = r
        moved *= weight
        stats.per_device_bytes += moved
        stats.op_bytes[op] = stats.op_bytes.get(op, 0.0) + moved
        stats.op_counts[op] = stats.op_counts.get(op, 0) + int(weight)


@dataclass
class Roofline:
    """Three-term roofline for one compiled (arch x shape x mesh) cell."""

    arch: str
    shape: str
    mesh: str
    chips: int
    # Primary terms: analytic op-accounting (costmodel.py); raw XLA
    # cost_analysis numbers are recorded alongside (while bodies counted
    # once — see costmodel.py docstring).
    hlo_flops_per_device: float
    hlo_bytes_per_device: float
    raw_cost_analysis_flops: float = 0.0
    raw_cost_analysis_bytes: float = 0.0
    collective_bytes_per_device: float = 0.0
    model_flops: float = 0.0  # 6 * N_active * D tokens (training) or fwd equivalent
    per_device_memory_bytes: float = 0.0
    op_bytes: dict[str, float] = field(default_factory=dict)
    op_counts: dict[str, int] = field(default_factory=dict)
    device_kind: str = V5E

    @property
    def peaks(self) -> DevicePeaks:
        return device_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.hlo_flops_per_device / self.peaks.bf16_flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes_per_device / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_device / self.peaks.ici_link_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (chips * HLO_FLOPs): how much compiled compute is
        'useful' model math (catches remat/redundancy waste)."""
        total = self.hlo_flops_per_device * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU bound = useful compute time / bound step time."""
        t_useful = self.model_flops / (self.chips * self.peaks.bf16_flops)
        lb = self.step_time_lower_bound
        return t_useful / lb if lb else 0.0

    def to_dict(self) -> dict[str, Any]:
        d = asdict(self)
        d.update(
            t_compute=self.t_compute,
            t_memory=self.t_memory,
            t_collective=self.t_collective,
            bottleneck=self.bottleneck,
            useful_flops_fraction=self.useful_flops_fraction,
            roofline_fraction=self.roofline_fraction,
            step_time_lower_bound=self.step_time_lower_bound,
        )
        return d


def model_flops(cfg, shape, n_params_active: int) -> float:
    """6*N*D for training, 2*N*D for inference forward passes."""
    if shape.kind == "train":
        return 6.0 * n_params_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_params_active * shape.tokens
    # decode: one token per sequence
    return 2.0 * n_params_active * shape.global_batch


def predict_decode_step(
    cfg,
    n_params: int,
    batch: int,
    mesh_shape: tuple[int, int] = (1, 1),
    dtype_bytes: int = 2,
    device_kind: str = V5E,
) -> Roofline:
    """Analytic roofline for ONE sharded decode step (no HLO needed).

    The serving sweep records this next to measured ``itl_p50`` so the
    B15 benchmark can report measured/predicted ratios per mesh. Terms:

      * compute — 2*N*B flops over ``data*model`` chips,
      * memory  — every device streams its 1/model weight shard once per
        step (decode is weight-bandwidth-bound; KV reads are second-order
        at serving batch sizes and deliberately excluded from the bound),
      * collective — tensor parallelism's two all-reduces per layer
        (attention o-proj + mlp down-proj) of (B, d_model) activations,
        ring cost ``2 * x * (model-1)/model`` each; zero at model=1.
    """
    data, model = (int(x) for x in mesh_shape)
    chips = max(data * model, 1)
    model = max(model, 1)
    flops = 2.0 * n_params * batch / chips
    weight_bytes = n_params * dtype_bytes / model
    act = batch * cfg.d_model * dtype_bytes
    coll = 2.0 * cfg.n_layers * (2.0 * act * (model - 1) / model)
    return Roofline(
        arch=cfg.name,
        shape=f"decode_b{batch}",
        mesh=f"{data}x{model}",
        chips=chips,
        hlo_flops_per_device=flops,
        hlo_bytes_per_device=weight_bytes,
        collective_bytes_per_device=coll,
        model_flops=2.0 * n_params * batch,
        per_device_memory_bytes=weight_bytes,
        device_kind=device_kind,
    )


def format_table(rows: list[Roofline]) -> str:
    hdr = (
        f"{'arch':26s} {'shape':12s} {'mesh':9s} {'t_comp(s)':>10s} {'t_mem(s)':>10s} "
        f"{'t_coll(s)':>10s} {'bound':>10s} {'useful%':>8s} {'roofl%':>7s} {'GB/dev':>7s}"
    )
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:26s} {r.shape:12s} {r.mesh:9s} {r.t_compute:>10.4f} "
            f"{r.t_memory:>10.4f} {r.t_collective:>10.4f} {r.bottleneck:>10s} "
            f"{100*r.useful_flops_fraction:>7.1f}% {100*r.roofline_fraction:>6.1f}% "
            f"{r.per_device_memory_bytes/2**30:>7.2f}"
        )
    return "\n".join(lines)
