"""Set-up from the program's own records: the split of ``setup_s`` that the
seven ``setup.*`` readers report (layer "set-up").

The program puts its set-up on the flight recorder on one clock from the
process's start (``utils/tracing.py``): a ``process_start`` event,
``package_import`` at the package's first line, spans on track ``setup``
(``import`` up to the program's first ask of the backend, which flag
parsing makes, or ``train.supcon.enable_compile_cache``, whichever is
first; ``store`` around ``device_store.make_store``) and JAX's ``trace``, ``lower`` and
``backend_compile`` spans of every program on track ``compile``. The
harness marks ``bench_window_start``. The total is process start to that
mark, and ``parts`` splits it, in this order of precedence, each part less
every second a part before it holds, so that the parts and the
unattributed rest sum to the total:

- ``compile``: union of the ``backend_compile`` spans (XLA's compile or the
  persistent cache's read);
- ``trace_lower``: union of the ``trace`` and ``lower`` spans (a function
  traced inside another has a span inside the outer one: unions, never
  sums);
- ``boot``: ``process_start`` to ``package_import`` (interpreter, ``import
  jax``, and the harness's ``require_tpu``, which starts the TPU);
- ``import``: the ``import`` span;
- ``store``: the ``store`` span;
- ``first_window``: the end of ``first_step`` (track ``main:compile``) to
  the window's start: the first flush window's other steps, its drain and
  the epoch-top copy.

Nothing without ``process_start`` and the window's mark: a program that
records no set-up gives every reader None, and a part whose records are
missing is left out.
"""

WINDOW_START = "bench_window_start"


def _union(spans):
    out = []
    for a, b in sorted(spans):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [tuple(x) for x in out]


def _minus(spans, cover):
    out = []
    for a, b in spans:
        for c, d in cover:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c))
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append((a, b))
    return out


def parts(run):
    """``{"total": s, "unattributed": s, <part>: s, ...}`` or None."""
    records = run["records"]
    marks = {}
    for r in records:
        if r.get("ph") != "X" and r["name"] in ("process_start", "package_import", WINDOW_START):
            marks.setdefault(r["name"], r["ts"])
    if "process_start" not in marks or WINDOW_START not in marks:
        return None
    t0, t1 = marks["process_start"], marks[WINDOW_START]

    def spans(keep):
        found = [(max(r["ts"], t0), min(r["ts"] + r["dur"], t1)) for r in records
                 if r.get("ph") == "X" and keep(r) and r["ts"] < t1 and r["ts"] + r["dur"] > t0]
        return _union(found) if found else None

    candidates = [
        ("compile", spans(lambda r: r["track"] == "compile" and r["name"] == "backend_compile")),
        ("trace_lower", spans(lambda r: r["track"] == "compile" and r["name"] in ("trace", "lower"))),
        ("boot", [(t0, marks["package_import"])] if "package_import" in marks else None),
    ]
    candidates += [(name, spans(lambda r, n=name: r["track"] == "setup" and r["name"] == n))
                   for name in ("import", "store")]
    first = [r for r in records if r.get("ph") == "X" and r["name"] == "first_step"
             and r["track"] == "main:compile" and r["ts"] < t1]
    candidates.append(("first_window", [(first[0]["ts"] + first[0]["dur"], t1)] if first else None))
    out, covered = {"total": t1 - t0}, []
    for name, found in candidates:
        if found is None:
            continue
        own = _minus(_union(found), covered)
        out[name] = sum(b - a for a, b in own)
        covered = _union(covered + own)
    out["unattributed"] = out["total"] - sum(v for k, v in out.items() if k != "total")
    return out


def part(run, name):
    got = parts(run)
    return None if got is None else got.get(name)
