"""Static compile-and-cost accounting for jitted entry points.

Chip time is budgeted and CPU walls are not device metrics, so the
static side of the performance layer is anchored on facts that are
DETERMINISTIC for a given (HLO, XLA version, platform) triple
and need no timer:

- ``cost_analysis()``: XLA's static FLOP and bytes-accessed count for
  the optimized executable — the O(n^2 d) Krum/Bulyan distance engine
  shows up here as real numbers per compiled round program;
- ``memory_analysis()``: argument/output/temp/alias buffer sizes, from
  which a peak-usage proxy is derived (jaxlib 0.4's
  ``CompiledMemoryStats`` has no explicit peak field on CPU).

:func:`analyze_lowered` runs ``.compile()`` on a ``jax.stages.Lowered``
ONCE, times the compile, attributes it to the persistent compile cache
(hit / miss / uncached) and returns a :class:`CostRecord`.  The records
feed the versioned ``compile`` / ``cost`` event kinds
(utils/metrics.py schema v2), the ``report`` subcommand's
"compile & cost" table, ``bench.py`` metadata, and the deterministic
perf-regression gate (tools/perf_gate.py) — which can therefore run on
CPU, without a TPU or a stopwatch.

Cache attribution is two-source:

- a process-wide hit/miss counter fed by jax's own monitoring events
  (``/jax/compilation_cache/cache_hits`` / ``cache_misses``), installed
  lazily by :func:`install_cache_counters`, which also logs every
  backend compile by module name (:func:`compile_log`);
- a before/after scan of the cache directory: a compile that ADDS an
  entry is a certain miss even if monitoring is silent.

A compile that neither bumped a counter nor wrote an entry is reported
``uncached`` (persistent cache disabled, or the compile finished under
``jax_persistent_cache_min_compile_time_secs``).

Stage & wire ledger (ISSUE 15).  The whole-program numbers above answer
"what does a round cost"; two further instruments answer "where":

- **Stage attribution**: the engines annotate their round programs with
  :func:`stage_scope` — ``jax.named_scope`` under the canonical stage
  set :data:`STAGES` (``deliver → quarantine → protect →
  tier1_aggregate → tier2_aggregate → apply``), and with the
  sub-stages :data:`SUBSTAGES` under ``deliver`` and
  ``tier1_aggregate`` that only the measured booking (utils/walls.py)
  reads.  The scopes are
  metadata-only: the optimized HLO stays computation-identical
  (:func:`canonical_hlo` strips op metadata and canonicalizes value
  names, so :func:`hlo_fingerprint` hashes the same program with scopes
  on or off — ``tools/perf_gate.py --stageproof`` proves it per pinned
  cell).  :func:`stage_attribution` then walks the annotated HLO text,
  models per-instruction FLOPs/bytes from opcode+shapes, buckets each
  instruction by the stage token in its ``op_name`` path, and
  partitions the *actual* whole-program totals proportionally to the
  modeled masses — so stage sums equal the program totals exactly by
  construction, and ``coverage`` reports the modeled share that landed
  in a named stage.

- **Wire ledger**: :func:`wire_ledger` prices every protocol seam a
  round crosses (broadcast down, client→tier-1 updates, tier-1→tier-2
  all_gather, secagg mask exchange + dropout recovery, async delivery
  ring) in bytes per round from the topology parameters alone.  The
  hierarchical ``tier1_to_tier2`` seam is ``S·d·4`` — the same number
  the SPMD round's measured ``collective_bytes`` pins (PR 12), which
  ``--stageproof`` cross-checks.  Both instruments emit as schema-v9
  events (``stage_cost`` / ``wire_bytes``) via CompileLedger.emit.
"""

from __future__ import annotations

import dataclasses
import os
import time
from typing import Optional

# Canonical stage set, in round order.  ``deliver`` covers batch
# gather + client update + attack craft (and the async delivery ring);
# ``quarantine`` the fault-injection screen + async re-mask;
# ``protect`` the secagg mask/unmask protocol; the two aggregate stages
# the tier-1 defense kernel and the tier-2 shard reduction; ``apply``
# the server momentum/LR update (+ round diagnostics riders).
STAGES = ("deliver", "quarantine", "protect",
          "tier1_aggregate", "tier2_aggregate", "apply")
_STAGE_SET = frozenset(STAGES)
# Sub-stages, each under its parent stage's scope: what ``deliver`` and
# ``tier1_aggregate`` lump together.  ``gather`` is the participation
# draw + batch gather + style/augment + the reshape into local steps,
# ``client_step`` the vmapped client update, ``craft`` the attacker's
# rewrite of rows [0, f); ``gram`` the pairwise squared distances
# (ops/distances.py), ``select`` Krum's scoring, sort / top-k and argmin.
# Inside ``client_step``, a sequence model's own two (models/sequence.py):
# ``attention`` (scores, mask, softmax, values; forward and backward, both
# kinds of layer) and ``experts`` (top-k, dispatch, the grouped products,
# combine; not the router's matmul).  With innermost booking
# ``client_step`` then holds the rest: projections, norms, head and loss,
# the row's write.
# Only the measured booking (utils/walls.py) reads them:
# :func:`stage_attribution` and ``hlo_stage_map`` filter on
# :data:`STAGES`, so an op under ``deliver/gather`` still books to
# ``deliver`` there.
SUBSTAGES = {"gather": "deliver", "client_step": "deliver",
             "craft": "deliver",
             "attention": "deliver", "experts": "deliver",
             "gram": "tier1_aggregate", "select": "tier1_aggregate"}

_STAGE_ENV = "FL_STAGE_SCOPES"
_stage_scopes_on = True


def stage_scopes_enabled() -> bool:
    """Stage scopes are on unless FL_STAGE_SCOPES=0 (env, checked per
    trace so tests can flip it) or :func:`set_stage_scopes` disabled
    them (how --stageproof builds the scope-free twin program)."""
    if os.environ.get(_STAGE_ENV, "1") == "0":
        return False
    return _stage_scopes_on


def set_stage_scopes(enabled: bool) -> bool:
    """Process-wide stage-scope switch; returns the previous value."""
    global _stage_scopes_on
    prev = _stage_scopes_on
    _stage_scopes_on = bool(enabled)
    return prev


def stage_scope(name: str):
    """``jax.named_scope(name)`` for a canonical stage or sub-stage
    (:data:`SUBSTAGES`, entered under its parent's scope) — metadata-only
    annotation (op_name path component) on every op traced under it,
    or a no-op context when scopes are disabled.  Importable without
    jax; jax loads on first enabled use."""
    assert name in _STAGE_SET or name in SUBSTAGES, (
        f"unknown stage {name!r} (stages: {STAGES}, "
        f"sub-stages: {tuple(SUBSTAGES)})")
    if not stage_scopes_enabled():
        import contextlib

        return contextlib.nullcontext()
    import jax

    return jax.named_scope(name)


# Cost-analysis keys we surface (cost_analysis() returns many more
# per-operand utilization entries; these are the stable, comparable ones).
_COST_KEYS = {"flops": "flops", "bytes accessed": "bytes_accessed"}


@dataclasses.dataclass
class CostRecord:
    """Static facts for one compiled entry point.

    ``flops`` / ``bytes_accessed`` are exact for a given (HLO, XLA,
    platform); ``peak_bytes`` is the argument+output+temp−alias proxy
    (an upper bound on resident executable memory, compared with a
    tolerance by the perf gate).  ``collective_bytes`` sums the output
    bytes of every cross-device collective in the compiled (post-SPMD)
    program — 0 for single-device programs, the wire-traffic witness
    for sharded ones (tools/perf_gate.py ``--shardproof`` pins the
    hierarchical SPMD round at O(S·d)).  ``cache`` is 'hit' | 'miss' |
    'uncached'; ``compile_s`` is the observed ``.compile()`` wall time
    (diagnostic only — never gated on)."""

    name: str
    platform: str
    flops: float = -1.0
    bytes_accessed: float = -1.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    alias_bytes: int = 0
    generated_code_bytes: int = 0
    collective_bytes: int = 0
    compile_s: float = 0.0
    cache: str = "uncached"
    # Per-stage partition of the totals above (stage_attribution output;
    # None when the backend withheld HLO text).  Deliberately NOT part
    # of gate_facts — the attribution is derived from the same program
    # the exact facts already pin.
    attribution: Optional[dict] = None

    @property
    def peak_bytes(self) -> int:
        return (self.argument_bytes + self.output_bytes + self.temp_bytes
                - self.alias_bytes)

    def cost_event(self) -> dict:
        """Payload for a 'cost' event (metrics.py schema v2)."""
        return dict(kind="cost", name=self.name, flops=self.flops,
                    bytes_accessed=self.bytes_accessed,
                    peak_bytes=self.peak_bytes,
                    argument_bytes=self.argument_bytes,
                    output_bytes=self.output_bytes,
                    temp_bytes=self.temp_bytes,
                    generated_code_bytes=self.generated_code_bytes,
                    collective_bytes=self.collective_bytes)

    def compile_event(self) -> dict:
        """Payload for a 'compile' event (metrics.py schema v2)."""
        return dict(kind="compile", name=self.name,
                    compile_s=round(self.compile_s, 4), cache=self.cache,
                    platform=self.platform)

    def stage_event(self) -> Optional[dict]:
        """Payload for a 'stage_cost' event (metrics.py schema v9), or
        None when no attribution was computable for this entry."""
        if self.attribution is None:
            return None
        att = self.attribution
        return dict(kind="stage_cost", name=self.name,
                    stages=att["stages"],
                    unattributed=att["unattributed"],
                    coverage=att["coverage"])

    def gate_facts(self) -> dict:
        """The facts tools/perf_gate.py diffs: exact ones first, then
        the tolerance-compared memory sizes."""
        return {"flops": self.flops, "bytes_accessed": self.bytes_accessed,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes,
                "peak_bytes": self.peak_bytes,
                "collective_bytes": self.collective_bytes}


# --- persistent-cache hit/miss accounting ------------------------------

class _CacheCounters:
    hits = 0
    misses = 0
    installed = False
    compiles: list = []     # one record per backend compile, in order
    trace_lower: list = []  # one record per jaxpr trace / MLIR lowering
    booked = (0, 0)         # (hits, misses) already attributed


_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# The compile pipeline's two stages before the backend: tracing the
# Python function to a jaxpr and lowering the jaxpr to an MLIR module.
_TRACE_LOWER_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "jaxpr_trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jaxpr_to_mlir",
}


def install_cache_counters() -> None:
    """Count persistent-compile-cache hits/misses process-wide via jax's
    monitoring events, and log every backend compile with its module
    name, seconds and cache attribution (:func:`compile_log`).
    Idempotent."""
    if _CacheCounters.installed:
        return
    _CacheCounters.installed = True
    import jax

    def listen(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            _CacheCounters.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _CacheCounters.misses += 1

    def listen_duration(event, secs, fun_name=None, **kw):
        # The hit/miss events carry no module name, but they fire inside
        # the compile they belong to — before its duration event — so
        # the counter delta since the last compile attributes them.
        if event in _TRACE_LOWER_EVENTS:
            _CacheCounters.trace_lower.append(
                {"stage": _TRACE_LOWER_EVENTS[event], "name": fun_name,
                 "secs": secs, "t": time.perf_counter()})
            return
        if event != _BACKEND_COMPILE_EVENT:
            return
        hits0, misses0 = _CacheCounters.booked
        _CacheCounters.booked = (_CacheCounters.hits, _CacheCounters.misses)
        cache = ("hit" if _CacheCounters.hits > hits0
                 else "miss" if _CacheCounters.misses > misses0
                 else "uncached")
        _CacheCounters.compiles.append(
            {"name": fun_name, "compile_s": round(secs, 3), "cache": cache})

    jax.monitoring.register_event_listener(listen)
    jax.monitoring.register_event_duration_secs_listener(listen_duration)


def cache_counts() -> dict:
    """Process-wide persistent-cache hit/miss totals (zeros until
    install_cache_counters ran AND a cached compile happened)."""
    return {"hits": _CacheCounters.hits, "misses": _CacheCounters.misses}


def compile_log() -> list:
    """Every backend compile since install_cache_counters, in order:
    ``{"name", "compile_s", "cache"}`` with cache 'hit' (loaded from the
    persistent cache), 'miss' (compiled and written) or 'uncached'
    (compiled, under the persistence threshold or cache disabled)."""
    return list(_CacheCounters.compiles)


def trace_lower_log() -> list:
    """Every jaxpr trace and jaxpr-to-MLIR lowering since
    install_cache_counters, in order: ``{"stage": "jaxpr_trace" |
    "jaxpr_to_mlir", "name", "secs", "t"}`` — the part of a first call
    that ``backend_compile_duration`` (:func:`compile_log`) leaves out.
    ``t`` is ``time.perf_counter()`` when the stage ended.  A nested jit
    traces inside its caller's trace, so the ``jaxpr_trace`` entries
    overlap: take the union of the intervals ``[t - secs, t]``, not the
    sum of ``secs``."""
    return list(_CacheCounters.trace_lower)


def compilation_cache_dir() -> Optional[str]:
    """The active persistent-cache directory, or None when disabled."""
    import jax

    try:
        path = jax.config.jax_compilation_cache_dir
    except AttributeError:
        path = None
    return path or None


def _cache_entries(path: Optional[str]) -> Optional[frozenset]:
    if not path or not os.path.isdir(path):
        return None
    try:
        return frozenset(f for f in os.listdir(path)
                         if not f.endswith("-atime"))
    except OSError:
        return None


# --- collective (cross-device) traffic accounting ----------------------

# Collective ops as they appear in optimized HLO text; async pairs
# (-start/-done) are counted once via -start, and '-done' is excluded
# so the same transfer is never double-billed.
_COLLECTIVE_RE = None

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
                "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}


def collective_hlo_bytes(text: str) -> dict:
    """Sum output bytes of every cross-device collective in an HLO
    module text (compiled/post-SPMD: shapes are per-device, so the
    totals are what one device moves).  Returns ``{'total': int,
    'per_op': {op: bytes}}``; 0/empty for single-device programs.

    The byte count is the op's OUTPUT shape(s) — the received data,
    the convention the perf gate's O(S·d) bound is written against
    (an all-gather's output is the gathered matrix; a ppermute's is
    one block)."""
    import re

    global _COLLECTIVE_RE
    if _COLLECTIVE_RE is None:
        _COLLECTIVE_RE = re.compile(
            r"=\s+(?P<out>[^=]*?)\s+"
            r"(?P<op>all-gather|all-reduce|reduce-scatter|"
            r"collective-permute|all-to-all)(?P<start>-start)?\(")
    shape_re = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
    per_op: dict = {}
    for m in _COLLECTIVE_RE.finditer(text):
        op = m.group("op")
        nbytes = 0
        for dtype, dims in shape_re.findall(m.group("out")):
            width = _DTYPE_BYTES.get(dtype)
            if width is None:
                continue          # layout braces etc. never match here
            elems = 1
            for d in filter(None, dims.split(",")):
                elems *= int(d)
            nbytes += elems * width
        per_op[op] = per_op.get(op, 0) + nbytes
    return {"total": sum(per_op.values()), "per_op": per_op}


# --- canonical HLO (metadata-stripped computation identity) ------------

# One attribute blob: metadata={op_type="..." op_name="..." ...}.
# Brace-free except inside the quoted strings, which the alternation
# steps over — so op_name paths may contain anything but a quote.
_METADATA_RE = None
_VALUE_NAME_RE = None
# The module header's stack-frame tables (jax >= 0.9 prints them): a
# line "FileNames" / "FunctionNames" / "FileLocations" / "StackFrames"
# and numbered rows up to a blank line.  They index source positions,
# which a named_scope's ``with`` line shifts: metadata, like op_name.
_FRAME_TABLE_RE = None


def canonical_hlo(text: str) -> str:
    """The computation-identity view of an HLO module text: op metadata
    and the header's stack-frame tables stripped and every
    %value/%computation name rewritten to its
    first-appearance ordinal.  Two programs are computation-identical
    iff their canonical texts match — op_name scopes, source lines and
    instruction-id drift are all erased, while opcodes, shapes, operand
    wiring and attributes all still compare."""
    import re

    global _METADATA_RE, _VALUE_NAME_RE, _FRAME_TABLE_RE
    if _METADATA_RE is None:
        _METADATA_RE = re.compile(
            r",?\s*metadata=\{(?:[^{}\"]|\"[^\"]*\")*\}")
        _VALUE_NAME_RE = re.compile(r"%[\w.\-]+")
        _FRAME_TABLE_RE = re.compile(
            r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
            r"(?:\d+ .*\n)*\n?", re.MULTILINE)
    stripped = _FRAME_TABLE_RE.sub("", _METADATA_RE.sub("", text))
    names: dict = {}

    def rename(m):
        return names.setdefault(m.group(0), f"%v{len(names)}")

    return _VALUE_NAME_RE.sub(rename, stripped)


def hlo_fingerprint(text: str) -> str:
    """sha256 of :func:`canonical_hlo` — the hash the byte-identical-HLO
    gates compare now that stage scopes legally perturb metadata."""
    import hashlib

    return hashlib.sha256(canonical_hlo(text).encode()).hexdigest()


# --- per-stage static attribution --------------------------------------

# Instruction lines whose cost is carried elsewhere (callees are listed
# as their own computations and counted there; parameters/constants/
# tuple plumbing move no unique data):
_SKIP_OPS = frozenset({
    "parameter", "constant", "get-tuple-element", "tuple", "fusion",
    "while", "call", "conditional", "bitcast", "after-all",
    "opt-barrier", "partition-id", "replica-id",
})
# Elementwise-ish opcodes modeled at one FLOP per output element:
_EW_OPS = frozenset({
    "add", "subtract", "multiply", "divide", "maximum", "minimum",
    "abs", "negate", "exponential", "exponential-minus-one", "log",
    "log-plus-one", "power", "sqrt", "rsqrt", "cbrt", "tanh",
    "logistic", "sine", "cosine", "sign", "floor", "ceil",
    "round-nearest-afz", "round-nearest-even", "compare", "select",
    "clamp", "and", "or", "xor", "not", "shift-left",
    "shift-right-logical", "shift-right-arithmetic", "remainder",
    "atan2", "is-finite", "rng-bit-generator",
})

_INSTR_RE = None
_SHAPE_RE = None
_OPNAME_RE = None
_CDIMS_RE = None


def _shape_bytes_elems(shape_text: str):
    """[(bytes, elems)] for every dtype[dims] shape in a text span."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        width = _DTYPE_BYTES.get(dtype)
        if width is None:
            continue              # 'devices[8,1]' etc. never bill
        elems = 1
        for d in filter(None, dims.split(",")):
            elems *= int(d)
        out.append((elems * width, elems))
    return out


def _instr_flops(op: str, out_shapes, operand_text: str) -> float:
    """Modeled FLOPs for one instruction — a *mass* used only to split
    the program's actual totals proportionally, so relative fidelity is
    what matters, not absolute counts."""
    out_elems = sum(e for _, e in out_shapes)
    if op == "dot":
        contract = 1
        m = _CDIMS_RE.search(operand_text)
        lhs_dims = _SHAPE_RE.search(operand_text)
        if m and lhs_dims:
            dims = [int(d) for d in
                    filter(None, lhs_dims.group(2).split(","))]
            for idx in filter(None, m.group(1).split(",")):
                i = int(idx)
                if i < len(dims):
                    contract *= dims[i]
        return 2.0 * out_elems * contract
    if op == "convolution":
        ops = _shape_bytes_elems(operand_text)
        kernel = ops[1][1] if len(ops) > 1 else 1
        return 2.0 * out_elems * kernel
    if op in ("reduce", "reduce-window"):
        ops = _shape_bytes_elems(operand_text)
        return float(ops[0][1]) if ops else float(out_elems)
    if op == "sort":
        import math

        return out_elems * max(1.0, math.log2(max(out_elems, 2)))
    if op in _EW_OPS:
        return float(out_elems)
    return 0.0


def stage_attribution(text: str, totals: Optional[dict] = None) -> dict:
    """Partition whole-program cost per canonical stage from annotated
    HLO text.

    Walks every instruction line in the module (fusion/while bodies are
    their own computations, so each op is seen exactly once), models
    its FLOPs (opcode+shapes) and bytes (all typed shapes on the line),
    and buckets both by the first :data:`STAGES` token in the op's
    ``op_name`` metadata path — ``unattributed`` when no stage scope
    encloses it.  When ``totals`` carries the program's actual
    ``flops`` / ``bytes_accessed`` / ``temp_bytes`` (compiled_cost_facts),
    each metric is split proportionally to the modeled masses with the
    residual folded into ``unattributed`` — so the per-stage values sum
    to the program total *exactly*.  ``coverage`` is the modeled share
    attributed to named stages (the --stageproof ≥95% bar)."""
    import math
    import re

    global _INSTR_RE, _SHAPE_RE, _OPNAME_RE, _CDIMS_RE
    global _METADATA_RE
    if _METADATA_RE is None:
        canonical_hlo("")         # compile the shared metadata regex
    if _INSTR_RE is None:
        _INSTR_RE = re.compile(
            r"^\s*(?:ROOT\s+)?%[\w.\-]+\s*=\s*"
            r"(?P<shape>\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\]"
            r"(?:\{[^}]*\})?)\s+(?P<op>[\w\-]+)\(")
        _SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
        _OPNAME_RE = re.compile(r'op_name="([^"]*)"')
        _CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
    mass: dict = {s: {"flops": 0.0, "bytes": 0.0} for s in STAGES}
    mass["unattributed"] = {"flops": 0.0, "bytes": 0.0}
    for line in text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None or m.group("op") in _SKIP_OPS:
            continue
        nm = _OPNAME_RE.search(line)
        # Innermost stage token wins: an outer scope around a whole
        # call region (e.g. the hierarchical megabatch scan) attributes
        # the region's *plumbing* (carry writes, estimate stacking)
        # without clobbering the finer stages annotated inside it.
        sm = ([t for t in nm.group(1).split("/") if t in _STAGE_SET]
              if nm else None)
        stage = sm[-1] if sm else "unattributed"
        body = _METADATA_RE.sub("", line) if _METADATA_RE else line
        after = body.split(m.group("op") + "(", 1)
        operand_text = after[1] if len(after) > 1 else ""
        out_shapes = _shape_bytes_elems(m.group("shape"))
        mass[stage]["flops"] += _instr_flops(
            m.group("op"), out_shapes, operand_text)
        mass[stage]["bytes"] += sum(
            b for b, _ in _shape_bytes_elems(body))
    named_f = math.fsum(mass[s]["flops"] for s in STAGES)
    named_b = math.fsum(mass[s]["bytes"] for s in STAGES)
    total_f = named_f + mass["unattributed"]["flops"]
    total_b = named_b + mass["unattributed"]["bytes"]
    out = {
        "stages": {}, "unattributed": {},
        "coverage": {
            "flops": named_f / total_f if total_f else 0.0,
            "bytes_accessed": named_b / total_b if total_b else 0.0,
        },
    }
    # Metric → which modeled mass splits it.
    metric_mass = {"flops": "flops", "bytes_accessed": "bytes",
                   "temp_bytes": "bytes"}
    totals = totals or {}
    for metric, mkey in metric_mass.items():
        total = totals.get(metric)
        if total is None or total < 0:
            continue
        denom = math.fsum(mass[s][mkey] for s in STAGES) \
            + mass["unattributed"][mkey]
        shares = {}
        for s in STAGES:
            shares[s] = total * (mass[s][mkey] / denom) if denom else 0.0
            out["stages"].setdefault(s, {})[metric] = shares[s]
        # Residual → unattributed, so the partition sums exactly.
        out["unattributed"][metric] = total - math.fsum(
            shares[s] for s in STAGES)
    out["model_mass"] = {s: dict(v) for s, v in mass.items()}
    return out


# --- per-seam wire ledger ----------------------------------------------

# Every protocol seam a round can cross, in round order.  Absent seams
# (e.g. tier1_to_tier2 on a flat topology) are omitted, zero-byte seams
# (secagg on, nobody dropped) are kept — the column exists, it is empty.
WIRE_SEAMS = ("broadcast", "client_update", "tier1_to_tier2",
              "secagg_mask_exchange", "secagg_recovery",
              "async_delivery")


def wire_ledger(*, cohort: int, dim: int, grad_bytes: int = 4,
                topology: str = "flat", num_shards: Optional[int] = None,
                megabatch: Optional[int] = None, spmd_parts: int = 1,
                secagg: str = "off", key_bytes: int = 32,
                dropped: int = 0,
                async_buffer: Optional[int] = None) -> dict:
    """Bytes-per-round on every protocol seam, priced from the topology
    parameters alone (f32 model wire; ``grad_bytes`` prices a quantized
    client→server leg, ROADMAP item 4's baseline column).

    Seams: server→client ``broadcast`` (every cohort member pulls the
    d-dim f32 model), ``client_update`` (cohort·d·grad_bytes up),
    hierarchical ``tier1_to_tier2`` (S estimates to the tier-2 reducer
    — exactly the ``S·d·4`` the SPMD all_gather moves per device, the
    PR 12 measured-collective cross-check), secagg ``mask_exchange``
    (one pairwise key/masked-seed exchange per client pair — vanilla
    C(n,2), groupwise S·C(m,2)) + ``recovery`` (each dropout makes
    every survivor reveal one pairwise secret), and the ``async
    delivery`` ring (buffer-capacity updates of d·grad_bytes per round,
    the capacity bound on what one round can deliver)."""
    seams: dict = {}
    seams["broadcast"] = {"bytes": cohort * dim * 4}
    seams["client_update"] = {"bytes": cohort * dim * grad_bytes}
    if topology == "hierarchical" and num_shards:
        seams["tier1_to_tier2"] = {
            "bytes": num_shards * dim * 4,
            "collective": spmd_parts > 1,
        }
    if secagg != "off":
        if secagg == "groupwise" and num_shards and megabatch:
            pairs = num_shards * (megabatch * (megabatch - 1) // 2)
        else:
            pairs = cohort * (cohort - 1) // 2
        seams["secagg_mask_exchange"] = {"bytes": pairs * key_bytes}
        seams["secagg_recovery"] = {
            "bytes": dropped * max(cohort - 1, 0) * key_bytes}
    if topology == "async" and async_buffer:
        seams["async_delivery"] = {
            "bytes": async_buffer * dim * grad_bytes}
    return {
        "topology": topology, "cohort": cohort, "dim": dim,
        "grad_bytes": grad_bytes,
        "seams": seams,
        "total_bytes": sum(s["bytes"] for s in seams.values()),
    }


# --- per-entry-point analysis ------------------------------------------

def _first(d):
    """cost_analysis() returns a list of per-program dicts on this
    jaxlib (one element for single-device programs) but a bare dict on
    newer ones — normalize."""
    if isinstance(d, (list, tuple)):
        return d[0] if d else {}
    return d or {}


def compiled_cost_facts(compiled) -> dict:
    """Extract the deterministic facts from a ``jax.stages.Compiled``.
    Missing analyses (some backends return None) yield -1 sentinels so
    a reader can tell "not measured" from a real zero."""
    out = {"flops": -1.0, "bytes_accessed": -1.0, "argument_bytes": 0,
           "output_bytes": 0, "temp_bytes": 0, "alias_bytes": 0,
           "generated_code_bytes": 0, "collective_bytes": 0}
    try:
        ca = _first(compiled.cost_analysis())
    except Exception:
        ca = {}
    for key, field in _COST_KEYS.items():
        if key in ca:
            out[field] = float(ca[key])
    try:
        out["collective_bytes"] = collective_hlo_bytes(
            compiled.as_text())["total"]
    except Exception:
        pass                       # text unavailable on some backends
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        out["argument_bytes"] = int(ma.argument_size_in_bytes)
        out["output_bytes"] = int(ma.output_size_in_bytes)
        out["temp_bytes"] = int(ma.temp_size_in_bytes)
        out["alias_bytes"] = int(ma.alias_size_in_bytes)
        out["generated_code_bytes"] = int(ma.generated_code_size_in_bytes)
    return out


def analyze_lowered(name: str, lowered) -> CostRecord:
    """Compile a ``jax.stages.Lowered`` once; return its CostRecord.

    Cache attribution: monitoring counters are snapshotted around the
    compile (exact when they fire), with the cache-dir scan as
    the fallback witness — an entry added during the compile is a miss
    even when monitoring is unavailable."""
    import jax

    install_cache_counters()
    platform = jax.devices()[0].platform
    cdir = compilation_cache_dir()
    before = _cache_entries(cdir)
    hits0, misses0 = _CacheCounters.hits, _CacheCounters.misses
    t0 = time.perf_counter()
    compiled = lowered.compile()
    dt = time.perf_counter() - t0
    after = _cache_entries(cdir)
    if _CacheCounters.hits > hits0:
        cache = "hit"
    elif _CacheCounters.misses > misses0:
        cache = "miss"
    elif before is not None and after is not None and after - before:
        cache = "miss"
    else:
        cache = "uncached"
    facts = compiled_cost_facts(compiled)
    rec = CostRecord(name=name, platform=platform, compile_s=dt,
                     cache=cache, **facts)
    try:
        rec.attribution = stage_attribution(compiled.as_text(), facts)
    except Exception:
        rec.attribution = None     # text unavailable on some backends
    return rec


class CompileLedger:
    """Per-run collection of CostRecords (core/engine.py:cost_report
    fills one; report.py renders it as the compile & cost table)."""

    def __init__(self):
        self.records: list = []
        self.errors: list = []   # (name, message) for entries that
        # failed to lower/compile — kept out of records so the gate
        # never diffs a partial fact set silently
        self.wire: Optional[dict] = None   # wire_ledger() output —
        # core/engine.py:cost_report attaches the run's per-seam
        # bytes-on-wire so emit() can version it as one event

    def analyze(self, name: str, lowered) -> CostRecord:
        rec = analyze_lowered(name, lowered)
        self.records.append(rec)
        return rec

    def emit(self, logger) -> None:
        """Write one 'compile' + one 'cost' (+ one 'stage_cost' when
        attribution was computable) event per record, and one
        'wire_bytes' event when a wire ledger is attached."""
        for rec in self.records:
            logger.record(**rec.compile_event())
            logger.record(**rec.cost_event())
            stage = rec.stage_event()
            if stage is not None:
                logger.record(**stage)
        if self.wire is not None:
            logger.record(kind="wire_bytes", **self.wire)

    def summary(self) -> dict:
        """{name: gate_facts} — the shape PERF_BASELINE.json stores."""
        return {rec.name: rec.gate_facts() for rec in self.records}
