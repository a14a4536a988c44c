"""Trace-lint rule engine: program contracts checked on traced jaxprs.

Each rule inspects one :class:`TraceUnit` — a traced (never executed)
program plus its config context and the telemetry collective tally the
trace produced — and returns :class:`Violation`\\ s with site-named,
actionable messages.  The six shipped rules:

* :class:`CollectiveBudgetRule` — per-site collective count/byte
  ceilings from :mod:`.contracts`, cross-checked against the jaxpr's
  total collective op count so tallies and programs cannot drift;
* :class:`HostSyncRule` — host callbacks / infeed / outfeed / host
  transfers inside traced programs (a device_get-class sync inside a
  hot loop serializes the dispatch pipeline);
* :class:`DtypeRule` — silent f64 on device (and any extra
  config-forbidden dtypes, e.g. f32 histograms on an int-only
  quantized path);
* :class:`ConstantFoldRule` — closed-over constants / literal operands
  above a size threshold (the PR 4 ``%reduce.227`` 2s-constant-fold
  stall class);
* :class:`RetraceRule` — jaxpr-hash stability across repeated traces
  (boosting iterations, serve SHAPE_BUCKETS re-dispatch);
* :class:`DonationRule` — declared buffer donation must actually alias
  (donated in-aval matches an out-aval) on the score-update entries.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import ir
from .contracts import DonationContract, contract_for, resolve_limit

__all__ = ["Violation", "TraceUnit", "Rule", "CollectiveBudgetRule",
           "HostSyncRule", "DtypeRule", "ConstantFoldRule", "RetraceRule",
           "DonationRule", "DEFAULT_RULES", "run_rules"]


@dataclass(frozen=True)
class Violation:
    rule: str
    config: str
    site: str
    message: str
    severity: str = "error"

    def to_json(self) -> Dict[str, str]:
        return {"rule": self.rule, "config": self.config, "site": self.site,
                "message": self.message, "severity": self.severity}


@dataclass
class TraceUnit:
    """One traced matrix config handed to the rules.

    ``collectives`` is the telemetry ``note_collective`` delta produced
    *while tracing this program* (site -> {op, count, bytes});
    ``hashes`` the retrace probes: ``(label, jaxpr_hash)`` pairs where a
    label appearing with two different hashes is a retrace.
    """

    name: str
    jaxpr: Any = None                       # ClosedJaxpr (may be None)
    ctx: Dict[str, Any] = field(default_factory=dict)
    collectives: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    hashes: List[Tuple[str, str]] = field(default_factory=list)


class Rule:
    name = "rule"

    def check(self, unit: TraceUnit) -> List[Violation]:
        raise NotImplementedError

    def _v(self, unit: TraceUnit, site: str, message: str,
           severity: str = "error") -> Violation:
        return Violation(self.name, unit.name, site, message, severity)


class CollectiveBudgetRule(Rule):
    """Per-site collective op/count/byte ceilings.

    Validates the trace's telemetry tally against the contracts declared
    next to the collective code, then cross-checks the tally against the
    jaxpr itself: the program's total collective op count must equal the
    total tallied count, so an untallied collective (or a tally with no
    op behind it) is flagged even before any ceiling is exceeded."""

    name = "collective-budget"

    def check(self, unit: TraceUnit) -> List[Violation]:
        out: List[Violation] = []
        ctx = unit.ctx
        total_tallied = 0
        for site, rec in sorted(unit.collectives.items()):
            total_tallied += int(rec.get("count", 0))
            contract = contract_for(site)
            if contract is None:
                out.append(self._v(
                    unit, site,
                    f"collective site '{site}' ({rec.get('op')}, "
                    f"{rec.get('count')} call(s)) has no declared "
                    f"contract; declare one with "
                    f"analysis.contracts.collective_contract next to the "
                    f"note_collective call"))
                continue
            op = str(rec.get("op", ""))
            if contract.ops and op not in contract.ops:
                out.append(self._v(
                    unit, site,
                    f"site '{site}' tallied op '{op}' but its contract "
                    f"({contract.declared_in}) allows {contract.ops}"))
            max_count = resolve_limit(contract.max_count, ctx)
            count = int(rec.get("count", 0))
            if max_count is not None and count > max_count:
                out.append(self._v(
                    unit, site,
                    f"site '{site}' traced {count} collective(s); the "
                    f"contract in {contract.declared_in} allows "
                    f"{max_count} per traced program"))
            max_bpo = resolve_limit(contract.max_bytes_per_op, ctx)
            nbytes = int(rec.get("bytes", 0))
            if max_bpo is not None and count > 0 and \
                    nbytes > count * max_bpo:
                out.append(self._v(
                    unit, site,
                    f"site '{site}' moved {nbytes} bytes over {count} "
                    f"op(s) (mean {nbytes // max(count, 1)}); the contract "
                    f"in {contract.declared_in} budgets "
                    f"{max_bpo} bytes/op — a full-histogram payload "
                    f"leaked onto a sliced path?"))
            max_dcn = resolve_limit(contract.max_dcn_bytes_per_op, ctx)
            if max_dcn is not None and count > 0:
                # modeled cross-host slice of the mean per-op payload:
                # (H-1)/H of the bytes leave the host on a host-major
                # axis (contracts.dcn_fraction) — the pod-budget check
                # that fires at abstract W=64 before chips exist
                from .contracts import dcn_fraction
                dcn_bytes = int((nbytes / count) * dcn_fraction(ctx))
                if dcn_bytes > max_dcn:
                    out.append(self._v(
                        unit, site,
                        f"site '{site}' models {dcn_bytes} CROSS-HOST "
                        f"bytes/op at {ctx.get('hosts', 'derived')} "
                        f"host(s) (mean payload "
                        f"{nbytes // max(count, 1)} B); the contract in "
                        f"{contract.declared_in} budgets {max_dcn} DCN "
                        f"bytes/op — this path is not pod-safe"))
        if unit.jaxpr is not None and ctx.get("crosscheck_tally", True):
            in_program = sum(len(v) for v in
                             ir.collectives_of(unit.jaxpr).values())
            if in_program != total_tallied:
                out.append(self._v(
                    unit, "<program>",
                    f"traced program holds {in_program} collective op(s) "
                    f"but telemetry tallied {total_tallied}: a collective "
                    f"was added without a note_collective site (or a "
                    f"site fires off-trace) — contracts and tallies have "
                    f"drifted"))
        return out


class HostSyncRule(Rule):
    """Host round-trips inside traced programs.

    ``device_get`` / ``.item()`` never appear in a jaxpr (they act on
    concrete arrays between dispatches); what DOES appear — and silently
    serializes the async dispatch pipeline — is the callback family and
    host transfers.  Ops inside while/scan bodies are the hot-loop
    class the serving and boosting paths must never contain."""

    name = "host-sync"

    HOST_PRIMS = ("pure_callback", "io_callback", "debug_callback",
                  "callback", "infeed", "outfeed")

    def check(self, unit: TraceUnit) -> List[Violation]:
        if unit.jaxpr is None:
            return []
        out: List[Violation] = []
        for info in ir.iter_eqns(unit.jaxpr):
            hit = info.prim in self.HOST_PRIMS
            if not hit and info.prim == "device_put":
                # flag explicit transfers to host memory spaces only
                devices = info.eqn.params.get("devices", ())
                hit = any("host" in str(d).lower() for d in
                          (devices if isinstance(devices, (list, tuple))
                           else [devices]))
            if hit:
                where = " inside a hot loop (" + \
                    "/".join(info.path) + ")" if info.in_loop else ""
                out.append(self._v(
                    unit, info.prim,
                    f"host-sync primitive '{info.prim}'{where}: each call "
                    f"stalls the device until the host round-trip "
                    f"returns; move it out of the traced program or "
                    f"behind telemetry's trace-time tallies"))
        return out


class DtypeRule(Rule):
    """No silent f64 on device; config-forbidden dtypes stay out.

    Host-side np.float64 (model fields in models/gbdt.py, the linear
    solver's lstsq) never enters a jaxpr and is deliberately NOT
    flagged — the rule sees only traced device programs.  ``ctx`` keys:
    ``forbid_dtypes`` extends the default {float64}; ``allow_f64`` (for
    an explicit x64 config) clears it."""

    name = "dtype"

    def check(self, unit: TraceUnit) -> List[Violation]:
        if unit.jaxpr is None:
            return []
        forbid = set(unit.ctx.get("forbid_dtypes", ()))
        if not unit.ctx.get("allow_f64", False):
            forbid |= {"float64", "complex128"}
        if not forbid:
            return []
        out: List[Violation] = []
        seen = 0
        for info in ir.iter_eqns(unit.jaxpr):
            for v in info.eqn.outvars:
                aval = getattr(v, "aval", None)
                dt = str(getattr(aval, "dtype", ""))
                if dt in forbid:
                    seen += 1
                    if seen > 8:  # one promotion cascades; cap the noise
                        continue
                    shape = tuple(getattr(aval, "shape", ()))
                    out.append(self._v(
                        unit, info.prim,
                        f"'{info.prim}' produces {dt}{shape} on device"
                        + (" inside " + "/".join(info.path)
                           if info.path else "")
                        + "; quantized/TPU paths must stay in narrow "
                          "dtypes — cast on the host or fix the "
                          "promotion"))
        if seen > 8:
            out.append(self._v(
                unit, "<program>",
                f"... and {seen - 8} more forbidden-dtype eqns"))
        return out


class ConstantFoldRule(Rule):
    """Closed-over constants / literal operands above a size threshold.

    The compile-stall class: XLA constant-folds ops over large literal
    operands at compile time (an 8-device dry run spent >2s folding one
    argmax over an all-False constant); a big constant baked into the
    program is also re-shipped with every executable.  Threshold in
    elements via ``ctx['const_fold_max_elems']`` (default 2**16)."""

    name = "constant-fold-size"
    DEFAULT_MAX_ELEMS = 1 << 16

    def check(self, unit: TraceUnit) -> List[Violation]:
        if unit.jaxpr is None:
            return []
        limit = int(unit.ctx.get("const_fold_max_elems",
                                 self.DEFAULT_MAX_ELEMS))
        out: List[Violation] = []
        for const, path in ir.iter_consts(unit.jaxpr):
            shape = tuple(getattr(const, "shape", ()))
            elems = 1
            for d in shape:
                elems *= int(d)
            if elems > limit:
                where = "/".join(path) if path else "<top>"
                out.append(self._v(
                    unit, where,
                    f"closed-over constant {getattr(const, 'dtype', '?')}"
                    f"{shape} ({elems} elems > {limit}) baked into the "
                    f"program at {where}: pass it as an argument so XLA "
                    f"neither folds nor re-ships it (the cat_member "
                    f"constant-fold stall class)"))
        for lit, info in ir.literal_operands(unit.jaxpr, limit + 1):
            out.append(self._v(
                unit, info.prim,
                f"literal operand {lit.aval.dtype}{tuple(lit.aval.shape)} "
                f"inlined at '{info.prim}': lift it to an argument"))
        return out


class RetraceRule(Rule):
    """Jaxpr-hash stability across repeated traces.

    ``unit.hashes`` holds ``(label, hash)`` probes: the lint driver
    traces each program twice with freshly built same-shaped inputs
    (boosting iterations i and i+1; each serve bucket twice).  A label
    with two distinct hashes means XLA compiles again every iteration —
    the retrace/recompile budget is zero.  The compile-event counters
    jax.monitoring feeds telemetry (TrainRecord.compile_events) measure
    the same thing at run time; this rule catches it at trace time."""

    name = "retrace"

    def check(self, unit: TraceUnit) -> List[Violation]:
        by_label: Dict[str, List[str]] = {}
        for label, h in unit.hashes:
            by_label.setdefault(label, []).append(h)
        out: List[Violation] = []
        for label, hs in sorted(by_label.items()):
            if len(set(hs)) > 1:
                out.append(self._v(
                    unit, label,
                    f"program '{label}' traced to {len(set(hs))} distinct "
                    f"jaxprs across {len(hs)} same-shape traces "
                    f"(hashes {sorted(set(hs))}): every dispatch "
                    f"recompiles — hoist the varying Python value out of "
                    f"the trace or mark it static"))
        max_programs = unit.ctx.get("max_distinct_programs")
        if max_programs is not None:
            distinct = len({h for _, h in unit.hashes})
            if distinct > int(max_programs):
                out.append(self._v(
                    unit, "<ladder>",
                    f"{distinct} distinct compiled programs for "
                    f"{len(by_label)} labels exceeds the budget of "
                    f"{max_programs} (the serve SHAPE_BUCKETS ladder "
                    f"compiles one program per bucket, nothing more)"))
        return out


class DonationRule(Rule):
    """Declared buffer donation must be able to alias.

    For every :class:`~.contracts.DonationContract` the rule lowers the
    jitted entry on representative args and checks (a) the declaration
    survives to the lowering (``donate_argnums``), and (b) every donated
    input aval matches some output aval in shape+dtype — XLA only
    aliases exact matches, so a silent dtype/shape drift keeps both
    buffers live and doubles the score-update footprint."""

    name = "donation"

    def check(self, unit: TraceUnit) -> List[Violation]:
        contracts: Sequence[DonationContract] = unit.ctx.get(
            "donation_contracts", ())
        out: List[Violation] = []
        for c in contracts:
            out.extend(self.check_contract(c, unit))
        return out

    def check_contract(self, c: DonationContract,
                       unit: TraceUnit) -> List[Violation]:
        import jax
        out: List[Violation] = []
        try:
            fn = c.fn_ref()
            args = c.build_args()
            lowered = jax.jit(fn, donate_argnums=c.donate_argnums).lower(
                *args) if not hasattr(fn, "lower") else fn.lower(*args)
        except Exception as exc:  # lowering itself failed
            out.append(self._v(
                unit, c.name,
                f"donation contract '{c.name}' ({c.declared_in}) could "
                f"not be lowered: {exc}"))
            return out
        declared = getattr(lowered, "donate_argnums", None)
        if declared is not None and tuple(declared) != c.donate_argnums:
            out.append(self._v(
                unit, c.name,
                f"'{c.name}' declares donate_argnums={c.donate_argnums} "
                f"but the lowering carries {tuple(declared)}: the jit "
                f"wrapper dropped the donation"))
        # aval match: donated inputs must have an identically shaped+typed
        # output to alias with
        jaxpr = jax.make_jaxpr(fn)(*args) if not hasattr(fn, "lower") \
            else jax.make_jaxpr(lambda *a: fn(*a))(*args)
        in_avals = [v.aval for v in jaxpr.jaxpr.invars]
        out_avals = [v.aval for v in jaxpr.jaxpr.outvars]
        out_sigs = [(tuple(a.shape), str(a.dtype)) for a in out_avals]
        for argnum in c.donate_argnums:
            if argnum >= len(in_avals):
                out.append(self._v(
                    unit, c.name,
                    f"'{c.name}' donates argnum {argnum} but the entry "
                    f"takes {len(in_avals)} array args"))
                continue
            a = in_avals[argnum]
            sig = (tuple(a.shape), str(a.dtype))
            if sig not in out_sigs:
                out.append(self._v(
                    unit, c.name,
                    f"'{c.name}' donates arg {argnum} "
                    f"({sig[1]}{sig[0]}) but no output matches that "
                    f"shape+dtype — XLA cannot alias it, the donated "
                    f"score buffer is silently copied "
                    f"(outputs: {out_sigs})"))
        return out


DEFAULT_RULES: Tuple[Rule, ...] = (
    CollectiveBudgetRule(), HostSyncRule(), DtypeRule(), ConstantFoldRule(),
    RetraceRule(), DonationRule())


def run_rules(units: Sequence[TraceUnit],
              rules: Optional[Sequence[Rule]] = None) -> List[Violation]:
    """Run every rule over every unit, most-severe ordering preserved."""
    violations: List[Violation] = []
    for unit in units:
        for rule in (rules if rules is not None else DEFAULT_RULES):
            violations.extend(rule.check(unit))
    return violations
