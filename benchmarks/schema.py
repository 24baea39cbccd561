"""Checked-in schemas for the benchmark JSON artifacts.

CI archives three machine-readable artifacts per run and diffs them run
over run (the perf trajectory). Their shapes are load-bearing — a renamed
column silently breaks the trajectory tooling — so each writer's schema
is pinned here and validated by tests/test_bench_schema.py:

  BENCH_kernels.json  benchmarks/run.py    column dicts keyed by row name
                      (us_per_call, derived, backend, pipeline,
                      frac_of_peak — the fig8 roofline ladder columns —
                      plus the counter-measured macs_per_us /
                      packed_bytes columns)
  BENCH_cluster.json  fig9_cluster_scaling  {version, gemm, path, rows}
  BENCH_e2e.json      e2e_networks          {version, batch, rows}
  BENCH_trace.json    repro.obs             Chrome trace-event object +
                      the "repro" payload (counters, op counters,
                      dispatch log) — `check_trace`

Validation is dependency-free (no jsonschema): `SchemaError` carries the
JSON-path of the first offending field.
"""
import json
import numbers
import pathlib

from repro.kernels.common import PIPELINE_MODES


class SchemaError(ValueError):
    """An artifact field is missing, mistyped, or out of range."""


def _fail(path, msg):
    raise SchemaError(f"{path}: {msg}")


def _need(d, key, types, path, check=None):
    if key not in d:
        _fail(path, f"missing required field {key!r}")
    return _typed(d[key], types, f"{path}.{key}", check)


def _typed(v, types, path, check=None):
    # bool is an int subclass; never accept it where a number is expected
    if isinstance(v, bool) and bool not in (types if isinstance(
            types, tuple) else (types,)):
        _fail(path, f"expected {types}, got bool")
    if not isinstance(v, types):
        _fail(path, f"expected {types}, got {type(v).__name__}")
    if check is not None and not check(v):
        _fail(path, f"value {v!r} out of range")
    return v


_NUM = numbers.Real


# ------------------------------------------------------ BENCH_kernels ---

def _segment_bits_ok(v) -> bool:
    """Widest-first "|"-joined container widths, e.g. "8", "8|2", "8|4|2"."""
    parts = v.split("|")
    widths = [int(p) for p in parts if p in ("8", "4", "2")]
    return (len(widths) == len(parts) and len(parts) >= 1
            and widths == sorted(widths, reverse=True)
            and len(set(widths)) == len(widths))


def validate_kernels(payload) -> None:
    """benchmarks/run.py payload: per-column dicts keyed by row name."""
    us = _need(payload, "us_per_call", dict, "$")
    for name, v in us.items():
        _typed(v, _NUM, f"$.us_per_call.{name}", lambda x: x >= 0)
    for col, types, check in (
            ("derived", str, None),
            ("backend", str, None),
            ("pipeline", str, lambda v: v in PIPELINE_MODES),
            ("frac_of_peak", _NUM, lambda v: 0.0 <= v <= 1.0),
            ("macs_per_us", _NUM, lambda v: v >= 0),
            ("packed_bytes", int, lambda v: v >= 0),
            ("segment_bits", str, _segment_bits_ok)):
        d = _need(payload, col, dict, "$")
        for name, v in d.items():
            if name not in us:
                _fail(f"$.{col}.{name}", "row name not in us_per_call")
            _typed(v, types, f"$.{col}.{name}", check)


def validate_fig8_roofline(payload, bits=(8, 4, 2)) -> None:
    """The fig8 acceptance shape: per bit-width, a pipelined and a
    non-pipelined row, each carrying a frac_of_peak roofline column, with
    the pipelined fraction >= the exposed-DMA one."""
    validate_kernels(payload)
    frac, pipe = payload["frac_of_peak"], payload["pipeline"]
    for b in bits:
        off, db = f"fig8_{b}bit_off", f"fig8_{b}bit_double_buffer"
        for name, mode in ((off, "off"), (db, "double_buffer")):
            if name not in payload["us_per_call"]:
                _fail(f"$.us_per_call.{name}", "missing fig8 roofline row")
            if pipe.get(name) != mode:
                _fail(f"$.pipeline.{name}", f"expected {mode!r}")
            if name not in frac:
                _fail(f"$.frac_of_peak.{name}", "missing roofline column")
            for col in ("macs_per_us", "packed_bytes"):
                if name not in payload[col]:
                    _fail(f"$.{col}.{name}",
                          "missing counter-measured column")
        if frac[db] < frac[off]:
            _fail(f"$.frac_of_peak.{db}",
                  "pipelined roofline below the exposed-DMA one")


# ------------------------------------------------------ BENCH_cluster ---

def _rows(payload, path):
    rows = _need(payload, "rows", list, path)
    if not rows:
        _fail(f"{path}.rows", "empty rows")
    return rows


def validate_cluster(payload) -> None:
    """fig9_cluster_scaling payload."""
    _need(payload, "version", int, "$", lambda v: v == 1)
    gemm = _need(payload, "gemm", dict, "$")
    for k in ("M", "K", "N"):
        _need(gemm, k, int, "$.gemm", lambda v: v > 0)
    _need(payload, "path", str, "$")
    for i, r in enumerate(_rows(payload, "$")):
        p = f"$.rows[{i}]"
        _typed(r, dict, p)
        _need(r, "name", str, p)
        _need(r, "bits", int, p, lambda v: v in (8, 4, 2))
        _need(r, "devices", int, p, lambda v: v >= 1)
        _need(r, "us_per_call", _NUM, p, lambda v: v >= 0)
        _need(r, "speedup", _NUM, p, lambda v: v > 0)
        _need(r, "efficiency", _NUM, p, lambda v: v > 0)
        _need(r, "per_dev_flops", _NUM, p, lambda v: v > 0)
        _need(r, "coll_bytes", int, p, lambda v: v >= 0)
        _need(r, "proj_us_v5e", _NUM, p, lambda v: v > 0)


# ---------------------------------------------------------- BENCH_e2e ---

def validate_e2e(payload) -> None:
    """e2e_networks payload; per-layer rows omit the scaling columns."""
    _need(payload, "version", int, "$", lambda v: v == 1)
    _need(payload, "batch", int, "$", lambda v: v >= 1)
    for i, r in enumerate(_rows(payload, "$")):
        p = f"$.rows[{i}]"
        _typed(r, dict, p)
        _need(r, "name", str, p)
        _need(r, "net", str, p)
        _need(r, "layer", str, p)
        _need(r, "bits", (str, int), p)
        _need(r, "devices", int, p, lambda v: v >= 1)
        _need(r, "us_per_call", _NUM, p, lambda v: v >= 0)
        _need(r, "macs_per_image", int, p, lambda v: v > 0)
        for opt, types, check in (
                ("speedup", _NUM, lambda v: v > 0),
                ("efficiency", _NUM, lambda v: v > 0),
                ("bytes_streamed", int, lambda v: v > 0),
                ("proj_us_v5e", _NUM, lambda v: v > 0)):
            if opt in r:
                _typed(r[opt], types, f"{p}.{opt}", check)


# ----------------------------------------------------- BENCH_accuracy ---

_ACC_MODES = ("float", "ptq", "qat")
_ACC_PLANS = ("none", "uniform", "layer", "channel_group")


def _accuracy_row(r, p):
    _typed(r, dict, p)
    _need(r, "name", str, p)
    _need(r, "mode", str, p, lambda v: v in _ACC_MODES)
    _need(r, "plan", str, p, lambda v: v in _ACC_PLANS)
    _need(r, "w_bits", int, p, lambda v: v in (0, 2, 4, 8, 32))
    _need(r, "accuracy", _NUM, p, lambda v: 0 <= v <= 1)
    _need(r, "correct", int, p, lambda v: v >= 0)
    _need(r, "n", int, p, lambda v: v >= 1)
    if r["correct"] > r["n"]:
        _fail(p, f"correct {r['correct']} > n {r['n']}")
    _need(r, "packed_weight_bytes", int, p, lambda v: v >= 1)
    _need(r, "train_steps", int, p, lambda v: v >= 1)
    _need(r, "segmented_rules", int, p, lambda v: v >= 0)


def validate_accuracy(payload) -> None:
    """benchmarks/accuracy payload: accuracy-vs-packed-bytes Pareto rows
    (every accuracy an integer-path `forward_int` measurement), plus the
    acceptance gates. The gates are RECOMPUTED from the rows here — the
    stored booleans can't claim what the rows don't show:
      * uniform QAT >= uniform PTQ at W4 and W2,
      * no plan row strictly dominated by a same-mode uniform row,
      * the channel-group QAT plan has <= bytes and >= accuracy vs the
        per-layer QAT plan (same budget; granularity is the only delta).
    Smoke payloads keep the row schema but skip gate enforcement."""
    _need(payload, "version", int, "$", lambda v: v == 1)
    _need(payload, "net", str, "$")
    mode = _need(payload, "mode", str, "$",
                 lambda v: v in ("full", "smoke"))
    ds = _need(payload, "dataset", dict, "$")
    _need(ds, "name", str, "$.dataset")
    _need(ds, "seed", int, "$.dataset")
    _need(ds, "eval_images", int, "$.dataset", lambda v: v >= 1)
    _need(payload, "budget_frac", _NUM, "$", lambda v: 0 < v < 1)
    rows = _rows(payload, "$")
    for i, r in enumerate(rows):
        _accuracy_row(r, f"$.rows[{i}]")

    def pick(m, plan, bits=None):
        got = [r for r in rows if r["mode"] == m and r["plan"] == plan
               and (bits is None or r["w_bits"] == bits)]
        return got[0] if got else None

    for m in ("ptq", "qat"):
        for b in (8, 4, 2):
            if pick(m, "uniform", b) is None:
                _fail("$.rows", f"missing uniform row mode={m} w_bits={b}")
    acc = _need(payload, "acceptance", dict, "$")
    for key in ("qat_ge_ptq_w4", "qat_ge_ptq_w2", "plans_on_frontier",
                "fine_dominates_layer", "all"):
        _need(acc, key, bool, "$.acceptance")
    if mode == "smoke":
        return
    for b in (4, 2):
        q, p = pick("qat", "uniform", b), pick("ptq", "uniform", b)
        if q["accuracy"] < p["accuracy"]:
            _fail(f"$.acceptance.qat_ge_ptq_w{b}",
                  f"QAT ({q['accuracy']}) below PTQ ({p['accuracy']}) "
                  f"at W{b}")
    for m in ("ptq", "qat"):
        uni = [r for r in rows if r["mode"] == m and r["plan"] == "uniform"]
        for r in rows:
            if r["mode"] != m or r["plan"] not in ("layer",
                                                   "channel_group"):
                continue
            for u in uni:
                if (u["packed_weight_bytes"] <= r["packed_weight_bytes"]
                        and u["accuracy"] >= r["accuracy"]
                        and (u["packed_weight_bytes"]
                             < r["packed_weight_bytes"]
                             or u["accuracy"] > r["accuracy"])):
                    _fail("$.acceptance.plans_on_frontier",
                          f"{u['name']} dominates {r['name']}")
    fine, layer = pick("qat", "channel_group"), pick("qat", "layer")
    if fine is None or layer is None:
        _fail("$.rows", "missing qat plan rows (layer/channel_group)")
    if (fine["packed_weight_bytes"] > layer["packed_weight_bytes"]
            or fine["accuracy"] < layer["accuracy"]):
        _fail("$.acceptance.fine_dominates_layer",
              "channel-group plan does not dominate-or-match the "
              "per-layer plan")
    if not acc["all"]:
        _fail("$.acceptance.all", "gates hold but 'all' is false")


# -------------------------------------------------------- BENCH_trace ---

_TRACE_PHASES = ("X", "i", "B", "E", "M", "C")
_COUNTER_FIELDS = ("calls", "macs", "logical_bytes", "packed_bytes")


def check_trace(payload) -> None:
    """A `repro.obs` Chrome trace-event artifact: the trace-event object
    form (every event carries name/ph/ts; complete events a dur) plus
    the repo payload under "repro" (generic counters, per-(op, bits,
    backend, pipeline) op counters, the dispatch decision log)."""
    events = _need(payload, "traceEvents", list, "$")
    for i, e in enumerate(events):
        p = f"$.traceEvents[{i}]"
        _typed(e, dict, p)
        _need(e, "name", str, p)
        _need(e, "ph", str, p, lambda v: v in _TRACE_PHASES)
        _need(e, "ts", _NUM, p, lambda v: v >= 0)
        if e["ph"] == "X":
            _need(e, "dur", _NUM, p, lambda v: v >= 0)
        if "args" in e:
            _typed(e["args"], dict, f"{p}.args")
    repro = _need(payload, "repro", dict, "$")
    _need(repro, "version", int, "$.repro", lambda v: v == 1)
    counters = _need(repro, "counters", dict, "$.repro")
    for name, v in counters.items():
        _typed(v, _NUM, f"$.repro.counters.{name}")
    ops = _need(repro, "op_counters", dict, "$.repro")
    for key, bucket in ops.items():
        p = f"$.repro.op_counters.{key}"
        if len(key.split("|")) != 4:
            _fail(p, "key is not op|w{W}a{A}|backend|pipeline")
        _typed(bucket, dict, p)
        for f in _COUNTER_FIELDS:
            _need(bucket, f, int, p, lambda v: v >= 0)
    dispatch = _need(repro, "dispatch", list, "$.repro")
    for i, d in enumerate(dispatch):
        p = f"$.repro.dispatch[{i}]"
        _typed(d, dict, p)
        _need(d, "op", str, p)
        _need(d, "backend", str, p)
        _need(d, "backend_source", str, p)
        _need(d, "pipeline", str, p, lambda v: v in PIPELINE_MODES)
        _need(d, "pipeline_source", str, p)
        _need(d, "ts", _NUM, p, lambda v: v >= 0)


# ------------------------------------------------------------ dispatch ---

VALIDATORS = {
    "BENCH_kernels.json": validate_kernels,
    "BENCH_cluster.json": validate_cluster,
    "BENCH_e2e.json": validate_e2e,
    "BENCH_accuracy.json": validate_accuracy,
    "BENCH_trace.json": check_trace,
}


def validate_file(path) -> None:
    """Validate an artifact file, dispatching on its basename."""
    p = pathlib.Path(path)
    try:
        fn = VALIDATORS[p.name]
    except KeyError:
        raise SchemaError(
            f"{p.name}: no schema registered (known: "
            f"{sorted(VALIDATORS)})") from None
    fn(json.loads(p.read_text()))
