"""``BENCHMARK.json`` against the rules of its contract that a file can
be held to without a chip."""
import json
import re
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
M = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_keys_names_units():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert M["command"][1].startswith(M["paths"][0] + "/")
    for c in M["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(M["paths"][0] + "/") and (ROOT / c["file"]).is_file()
        held = json.loads((ROOT / c["file"]).read_text())
        assert held["source"] == c["source"] and held["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert NAME.match(k) and not re.search(
                r"(_dim|_rank)$|^(hidden|intermediate|head|state|latent)_size$|"
                r"expand|experts_per_tok", k)
    for w in M["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        assert w["config"] in {c["name"] for c in M["configs"]}
    for m in M["end_to_end"] + M["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in M["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.1
    for m in M["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert line(m["layer"])
    names = [x["name"] for k in ("configs", "workloads") for x in M[k]]
    names += [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(names) == len(set(names))
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_cell_reports_what_it_must():
    cells = {w["name"] for w in M["workloads"]}
    e2e = {m["name"]: set(m.get("workloads", cells)) for m in M["end_to_end"]}
    assert e2e["setup_s"] == cells
    for cell in cells:
        assert sum(cell in ws for n, ws in e2e.items() if n != "setup_s") >= 1
        assert any(cell in m.get("workloads", cells) for m in M["per_layer"])
    for m in M["per_layer"]:
        assert set(m.get("workloads", cells)) <= e2e[m["moves"]]
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(cells) // 4)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_a_full_check_fits_its_time():
    s = M["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
