"""The seven ``setup.*`` readers on synthetic records: each takes unions (a
nested trace counts once, a compile inside ``store`` is compile's), the six
seconds and the unattributed share of the total sum to process start ->
``bench_window_start``, and every reader reads None without its records."""

import os

import pytest

import run as harness

NAMES = ("setup.boot_s", "setup.import_s", "setup.store_s", "setup.trace_lower_s",
         "setup.compile_s", "setup.first_window_s")
SHARE = "setup.unattributed_share"


def x(name, track, ts, dur, **args):
    return {"name": name, "track": track, "ph": "X", "ts": ts, "dur": dur, "args": args}


def i(name, track, ts):
    return {"name": name, "track": track, "ph": "i", "ts": ts}


def records():
    """Process start at -20, the window at 10; records after the window
    count for nothing."""
    return [
        i("process_start", "setup", -20.0),
        i("package_import", "setup", -12.0),
        x("import", "setup", -12.0, 7.0),
        x("store", "setup", -3.0, 3.0),
        x("backend_compile", "compile", -2.0, 0.5, fun_name="jit(gather)", cache_hit=True),
        x("trace", "compile", 1.0, 2.0, fun_name="abstract_state"),
        x("trace", "compile", 1.5, 0.5, fun_name="add"),  # nested
        x("trace", "compile", 4.0, 1.0, fun_name="ring_update"),
        x("lower", "compile", 5.0, 1.0, fun_name="jit(ring_update)"),
        x("backend_compile", "compile", 6.0, 1.5, fun_name="jit(ring_update)", cache_hit=False),
        x("first_step", "main:compile", 4.0, 4.0, step=0),
        i("bench_window_start", "bench", 10.0),
        x("backend_compile", "compile", 11.0, 5.0, fun_name="late"),
        i("bench_window_end", "bench", 40.0),
    ]


def read(name, recs):
    return harness.load_reader(name).read({"records": recs})


def test_each_reader_reads_its_union():
    recs = records()
    assert read("setup.compile_s", recs) == pytest.approx(2.0)
    assert read("setup.trace_lower_s", recs) == pytest.approx(2.0 + 2.0)
    assert read("setup.boot_s", recs) == pytest.approx(8.0)
    assert read("setup.import_s", recs) == pytest.approx(7.0)
    assert read("setup.store_s", recs) == pytest.approx(2.5)  # less its compile
    assert read("setup.first_window_s", recs) == pytest.approx(2.0)
    # left: [-5, -3], [0, 1], [3, 4] and the first step's [7.5, 8]: 4.5 of 30
    assert read(SHARE, recs) == pytest.approx(100.0 * 4.5 / 30.0)


def test_the_parts_sum_to_the_total():
    recs = records()
    total = 10.0 - (-20.0)
    parts = sum(read(n, recs) for n in NAMES) + read(SHARE, recs) / 100.0 * total
    assert parts == pytest.approx(total)


@pytest.mark.parametrize("name", NAMES + (SHARE,))
def test_none_without_its_records(name):
    """Without the set-up record (a program that records none, as before
    these spans) every reader reads None; without its own records, the
    reader does."""
    recs = records()
    assert read(name, [r for r in recs if r["name"] != "process_start"]) is None
    assert read(name, [r for r in recs if r["name"] != "bench_window_start"]) is None
    own = {"setup.boot_s": "package_import", "setup.import_s": "import",
           "setup.store_s": "store", "setup.trace_lower_s": "trace",
           "setup.compile_s": "backend_compile", "setup.first_window_s": "first_step"}
    if name in own:
        drop = {own[name], "lower"} if name == "setup.trace_lower_s" else {own[name]}
        assert read(name, [r for r in recs if r["name"] not in drop]) is None
    # the parent's compile record: instants, which are no spans
    parent = [r for r in recs if r["track"] != "setup" and r["track"] != "compile"] + [
        dict(i("backend_compile", "compile", 6.0), args={"duration_s": 1.5})]
    assert read(name, parent) is None


@pytest.mark.parametrize("name", NAMES + (SHARE,))
def test_docstring_names_the_layer_and_what_it_moves(name):
    doc = harness.load_reader(name).__doc__
    assert 'layer "set-up"' in doc and "setup_s" in doc


def test_benchmark_json_lists_the_readers_for_every_cell():
    import json

    with open(os.path.join(harness.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NAMES + (SHARE,):
        m = listed[name]
        assert (m["layer"], m["moves"], m["source"], m["better"]) == (
            "set-up", "setup_s", "program_span", "lower")
        assert m["unit"] == ("%" if name == SHARE else "s") and m["workloads"] == cells
