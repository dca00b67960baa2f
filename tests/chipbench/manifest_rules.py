"""The rules every version of ``BENCHMARK.json`` keeps, as functions of a
manifest passed in, so that the real manifest and a rehearsed copy of it
are held to the same rules. Not a test module: the tests call these.

An entry is found by its name, never by where it stands. What is pinned
is the accepted manifest as a PREFIX (``fixtures/accepted-manifest-pr*.json``):
in each section the accepted entries stand first and in order, each as it
was but for a ``workloads`` list that may have grown at its end. So a PR
that appends a configuration, a cell, its metrics and the cell's name at
the end of accepted lists breaks no rule, and one that edits, reorders or
drops an accepted entry breaks one.

``extra`` maps a path under the repository to a parsed JSON file that is
looked up before the disk: a rehearsal holds its new files there.
"""

import functools
import importlib
import json
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))
FIX = ROOT / "tests/chipbench/fixtures"
SECTIONS = ("configs", "workloads", "end_to_end", "per_layer")
FIXED = ("command", "paths", "run_seconds")
ENTRY_KEYS = {"name", "unit", "better", "source", "layer", "moves",
              "workloads"}
SPEC_KEYS = {"layer", "unit", "moves", "reader", "args"}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# Entries taken out of the benchmark, by section: an accepted prefix is
# compared without them. Five rooflines that read nothing: three selected
# ``%closed_call`` (a Pallas call is named after its kernel now), two
# ``%ragged-dot-none`` (the grouped products are a kernel of their own).
TAKEN_OUT = {
    "per_layer": ("flash_prefill_roofline", "paged_decode_roofline",
                  "flash_train_roofline", "moe_experts_prefill_roofline",
                  "moe_experts_decode_roofline"),
}


def load(rel: str, extra: dict | None = None) -> dict:
    if extra and rel in extra:
        return extra[rel]
    return json.loads((ROOT / rel).read_text())


def exists(rel: str, extra: dict | None = None) -> bool:
    return bool(extra and rel in extra) or (ROOT / rel).exists()


def manifest() -> dict:
    return load("BENCHMARK.json")


def accepted(pr: int) -> dict:
    return json.loads((FIX / f"accepted-manifest-pr{pr}.json").read_text())


def accepted_prs() -> list[int]:
    """Every pinned accepted manifest, oldest first."""
    return sorted(int(p.stem.rsplit("pr", 1)[1])
                  for p in FIX.glob("accepted-manifest-pr*.json"))


def by_name(entries: list, name: str) -> dict:
    found = [e for e in entries if e["name"] == name]
    assert len(found) == 1, f"{name}: {len(found)} entries of that name"
    return found[0]


def names(bench: dict, section: str) -> list[str]:
    return [e["name"] for e in bench[section]]


def kept(entries: list, section: str) -> list:
    gone = TAKEN_OUT.get(section, ())
    return [e for e in entries
            if (e if isinstance(e, str) else e["name"]) not in gone]


@functools.cache
def table_layers() -> frozenset[str]:
    """The layers that the newest accepted manifest's metrics name."""
    return frozenset(m["layer"]
                     for m in accepted(max(accepted_prs()))["per_layer"])


def spec_of(metric: str, extra: dict | None = None) -> dict:
    return load(f"chipbench/metrics/{metric}.json", extra)


# -- rule 1: the accepted manifest stands first ------------------------------------

def check_prefix(bench: dict, was: dict, section: str) -> None:
    """``was``'s entries of ``section`` (less those taken out) stand first
    in ``bench``, in order, each unchanged but for a ``workloads`` list
    that may have grown at its end; ``command``, ``paths`` and
    ``run_seconds`` are ``was``'s."""
    old_entries, now = kept(was[section], section), bench[section]
    assert len(now) >= len(old_entries), f"{section}: accepted entry dropped"
    for old, new in zip(old_entries, now):
        assert new["name"] == old["name"], (
            f"{section}: accepted entry dropped or moved: {old['name']} "
            f"expected where {new['name']} stands")
        assert set(new) == set(old), f"accepted entry changed: {old['name']}"
        for key, value in old.items():
            if key == "workloads":
                assert new[key][:len(value)] == value, (
                    f"accepted list changed, not grown at its end: "
                    f"{old['name']}")
            else:
                assert new[key] == value, (
                    f"accepted entry changed: {old['name']}.{key}")
    for key in FIXED:
        assert bench[key] == was[key], f"accepted {key} changed"


def check_own_after(bench: dict, section: str, before: list[str],
                    own: list[str]) -> None:
    """The names ``before`` (less those taken out) stand first, in order;
    each of ``own`` is there once, anywhere after them."""
    before = kept(before, section)
    now = names(bench, section)
    assert now[:len(before)] == before, f"{section}: accepted names moved"
    for name in kept(own, section):
        assert now.count(name) == 1, f"{section}: {name} missing"
        assert now.index(name) >= len(before), (
            f"{section}: {name} stands among the accepted entries")


# -- rule 2: a configuration's own entries, found by name ----------------------------

def check_entry(bench: dict, name: str, cells: list[str] | None = None,
                extra: dict | None = None) -> None:
    """One ``per_layer`` entry: its keys, its file's fields, a layer of
    the table, a reader that exists, a count that can be called; every
    cell it lists exists and reports the end-to-end metric it moves. Its
    list starts with ``cells`` where they are given."""
    entry = by_name(bench["per_layer"], name)
    assert set(entry) == ENTRY_KEYS, f"{name}: keys {sorted(entry)}"
    spec = spec_of(name, extra)
    assert set(spec) == SPEC_KEYS, f"{name}: its file's keys {sorted(spec)}"
    assert (spec["layer"], spec["unit"], spec["moves"]) == (
        entry["layer"], entry["unit"], entry["moves"]), (
        f"{name}: entry disagrees with its file")
    assert entry["layer"] in table_layers(), (
        f"{name}: layer {entry['layer']!r} is not a layer of the table")
    reader = importlib.import_module(f"chipbench.readers.{spec['reader']}")
    assert callable(reader.read), f"{name}: reader has no read"
    if "counts" in spec["args"]:
        module, fn = spec["args"]["counts"].rsplit(".", 1)
        assert callable(getattr(
            importlib.import_module(f"chipbench.counts.{module}"), fn)), (
            f"{name}: count {spec['args']['counts']} is not callable")
    moved = by_name(bench["end_to_end"], entry["moves"])
    cells_now = names(bench, "workloads")
    assert entry["workloads"], f"{name}: lists no cell"
    for cell in entry["workloads"]:
        assert cell in cells_now, f"{name}: {cell} is no cell"
        assert "workloads" not in moved or cell in moved["workloads"], (
            f"{name}: {cell} does not report {entry['moves']}")
    if cells is not None:
        assert entry["workloads"][:len(cells)] == cells, (
            f"{name}: its list does not start with {cells}")


def check_cell(bench: dict, cell: str, config: str | None = None,
               chips: int | None = None, extra: dict | None = None) -> None:
    """One cell and its configuration, found by name, resolve to their
    files and to a driver that runs."""
    entry = by_name(bench["workloads"], cell)
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert config is None or entry["config"] == config, f"{cell}: config"
    assert chips is None or entry["chips"] == chips, f"{cell}: chips"
    assert entry["chips"] in (1, 4) and 1 <= len(entry["why"]) <= 200
    spec = load(f"chipbench/workloads/{cell}.json", extra)
    assert spec["config"] == entry["config"], f"{cell}: its file's config"
    cfg = by_name(bench["configs"], entry["config"])
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert exists(cfg["file"], extra), f"{cfg['name']}: no file"
    conf = load(cfg["file"], extra)
    for key in ("source", "reduced", "assumed", "departures"):
        assert key in conf, f"{cfg['name']}: its file has no {key}"
    assert conf["reduced"] == cfg["reduced"]
    driver = importlib.import_module(f"chipbench.drivers.{spec['driver']}")
    assert callable(driver.run)


# -- rule 3: which accepted lists a new cell may join --------------------------------

def may_join(metric: str, foreign: tuple = (),
             extra: dict | None = None) -> bool:
    """An accepted metric is asked of a cell of a new configuration only
    where its file carries no count (a count is its configuration's) and
    selects none of ``foreign`` (programs that are not the cell's)."""
    text = json.dumps(spec_of(metric, extra))
    return '"counts"' not in text and not any(p in text for p in foreign)


def check_joins(bench: dict, before: list[str], cell: str,
                foreign: tuple = (), extra: dict | None = None) -> None:
    """Every metric of ``before`` whose list now holds ``cell`` may be
    asked of it."""
    for name in kept(before, "per_layer"):
        entry = by_name(bench["per_layer"], name)
        if cell in entry["workloads"]:
            assert may_join(name, foreign, extra), (
                f"{name}: asked of {cell}, a count or program not its own")


# -- the whole manifest ---------------------------------------------------------

def check_names(bench: dict) -> None:
    """Names and units of the allowed characters, each name once; the
    bounds; the share of four-chip cells; every configuration used."""
    for section in SECTIONS:
        got = names(bench, section)
        assert len(got) == len(set(got)), f"{section}: a name twice"
        assert all(NAME.match(n) for n in got), got
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for w in bench["workloads"]:
        assert NAME.match(w["traffic"]) and NAME.match(w["config"])
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert all(0.01 <= m["bound"] <= 0.1 for m in bench["end_to_end"])
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4), (
        "too many four-chip cells")
    used = {w["config"] for w in bench["workloads"]}
    assert used == set(names(bench, "configs")), "a configuration unused"


def check_reports(bench: dict) -> None:
    """Every cell reports ``setup_s``, another end-to-end metric and a
    per-layer one."""
    for cell in names(bench, "workloads"):
        e2e = [m["name"] for m in bench["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]]
        assert "setup_s" in e2e and len(e2e) >= 2, f"{cell}: end to end"
        assert any(cell in m["workloads"] for m in bench["per_layer"]), (
            f"{cell}: no per-layer metric")


def check_manifest(bench: dict, extra: dict | None = None) -> None:
    """What holds of every entry: the two above, and every cell and
    metric resolving."""
    check_names(bench)
    check_reports(bench)
    for cell in names(bench, "workloads"):
        check_cell(bench, cell, extra=extra)
    for name in names(bench, "per_layer"):
        check_entry(bench, name, extra=extra)


def check_all(bench: dict, extra: dict | None = None) -> None:
    """Every rule: every accepted manifest as a prefix; for every cell of
    a configuration that an accepted manifest lacks, that manifest's
    lists it joined; then the whole manifest."""
    for pr in accepted_prs():
        was = accepted(pr)
        for section in SECTIONS:
            check_prefix(bench, was, section)
        old_configs = set(names(was, "configs"))
        for w in bench["workloads"]:
            if w["config"] not in old_configs:
                check_joins(bench, names(was, "per_layer"), w["name"],
                            extra=extra)
    check_manifest(bench, extra)
