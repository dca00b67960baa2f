"""The manifest's rules (``manifest_rules.py``) on ``BENCHMARK.json`` and on
rehearsals of the next configuration's append: a copy of the manifest,
made in memory, with one more configuration and one more cell, the cell's
name at the end of the accepted lists it may join, and two metrics of its
own. Every rule has to hold of each rehearsal; a rehearsal gone wrong
(an accepted entry edited, moved or dropped, a list joined at its front,
a count that is another configuration's) has to break the rule for it."""

import copy
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import manifest_rules as rules  # noqa: E402

NEWEST = rules.accepted(37)
# kind: (the cell it copies, the end-to-end metrics it reports, its
# chips, programs that are not its own)
REHEARSALS = {
    "serve": ("serve-diffuse", ("ttft_p95_ms", "tpot_p95_ms"), 1,
              ("chunk_step",)),
    "train": ("train-4k", ("train_tok_s",), 4, ()),
}


def rehearse(kind: str):
    """The manifest with a copy of one cell appended under new names, as
    the change that brings a new configuration appends it: the
    configuration and the cell at the ends of their lists, the cell at the end of its
    end-to-end metrics' lists and of every accepted list that it may
    join, and two metrics of its own that list it alone. Returns the
    copy, its new files by path, the cell and the two metrics."""
    twin, e2e, chips, foreign = REHEARSALS[kind]
    bench, extra = copy.deepcopy(rules.manifest()), {}
    cell, model = f"{kind}-rehearsed", f"{kind}-rehearsed-model"
    entry = rules.by_name(bench["workloads"], twin)
    config = rules.by_name(bench["configs"], entry["config"])
    path = f"chipbench/configs/{model}.json"
    bench["configs"].append(dict(config, name=model, file=path))
    bench["workloads"].append(dict(entry, name=cell, config=model,
                                   chips=chips))
    extra[path] = rules.load(config["file"])
    extra[f"chipbench/workloads/{cell}.json"] = dict(
        rules.load(f"chipbench/workloads/{twin}.json"), config=model)
    for m in bench["end_to_end"]:
        if m["name"] in e2e:
            m["workloads"].append(cell)
    for m in bench["per_layer"]:
        if twin in m["workloads"] and rules.may_join(m["name"], foreign):
            m["workloads"].append(cell)
    own = [m for m in bench["per_layer"] if m["workloads"] == [twin]][:2]
    mine = []
    for m in own:
        name = f"rehearsed_{m['name']}"
        bench["per_layer"].append(dict(m, name=name, workloads=[cell]))
        extra[f"chipbench/metrics/{name}.json"] = rules.spec_of(m["name"])
        mine.append(name)
    return bench, extra, cell, mine


@pytest.mark.parametrize("section", rules.SECTIONS)
def test_the_accepted_entries_come_first_and_their_lists_only_grew(section):
    """The newest accepted manifest is ``BENCHMARK.json`` as it stands
    with the five silent rooflines taken out: it is its own prefix."""
    rules.check_prefix(rules.manifest(), NEWEST, section)


def test_every_rule_holds_of_the_manifest():
    rules.check_all(rules.manifest())
    gone = set(rules.TAKEN_OUT["per_layer"])
    assert not gone & set(rules.names(rules.manifest(), "per_layer"))
    assert not [g for g in gone
                if (ROOT / f"chipbench/metrics/{g}.json").exists()]


@pytest.mark.parametrize("kind", list(REHEARSALS))
def test_a_rehearsed_append_keeps_every_rule(kind):
    bench, extra, cell, mine = rehearse(kind)
    twin, e2e, chips, foreign = REHEARSALS[kind]
    assert len(mine) == 2
    rules.check_all(bench, extra)
    rules.check_cell(bench, cell, f"{cell}-model", chips, extra)
    rules.check_own_after(bench, "workloads",
                          rules.names(NEWEST, "workloads"), [cell])
    rules.check_own_after(bench, "per_layer",
                          rules.names(NEWEST, "per_layer"), mine)
    for name in mine:
        rules.check_entry(bench, name, cells=[cell], extra=extra)
    rules.check_joins(bench, rules.names(NEWEST, "per_layer"), cell,
                      foreign, extra)
    for name in e2e:
        assert cell in rules.by_name(bench["end_to_end"], name)["workloads"]
    # it joined some accepted lists, and none that counts another model
    joined = [m["name"] for m in bench["per_layer"]
              if cell in m["workloads"] and m["name"] not in mine]
    assert joined and all(rules.may_join(n, foreign) for n in joined)


def _edit_accepted(bench, extra, cell, mine):
    rules.by_name(bench["per_layer"], "round_p50_ms")["unit"] = "s"


def _drop_accepted(bench, extra, cell, mine):
    bench["per_layer"].remove(rules.by_name(bench["per_layer"], "finish_ms"))


def _join_at_the_front(bench, extra, cell, mine):
    cells = rules.by_name(bench["end_to_end"], "ttft_p95_ms")["workloads"]
    cells.remove(cell)
    cells.insert(0, cell)


def _entry_among_the_accepted(bench, extra, cell, mine):
    bench["per_layer"].insert(3, bench["per_layer"].pop())


def _command_changed(bench, extra, cell, mine):
    bench["command"] = ["python3", "chipbench/run.py", "--fast"]


def _layer_not_in_the_table(bench, extra, cell, mine):
    rules.by_name(bench["per_layer"], mine[0])["layer"] = "nowhere"
    extra[f"chipbench/metrics/{mine[0]}.json"] = dict(
        extra[f"chipbench/metrics/{mine[0]}.json"], layer="nowhere")


def _file_disagrees(bench, extra, cell, mine):
    extra[f"chipbench/metrics/{mine[0]}.json"] = dict(
        extra[f"chipbench/metrics/{mine[0]}.json"], unit="s")


def _joins_a_count(bench, extra, cell, mine):
    rules.by_name(bench["per_layer"], "prefill_mfu_pct")["workloads"] \
        .append(cell)


def _moves_what_it_does_not_report(bench, extra, cell, mine):
    rules.by_name(bench["end_to_end"], "tpot_p95_ms")["workloads"] \
        .remove(cell)


FAULTS = [
    (_edit_accepted, "accepted entry changed: round_p50_ms.unit"),
    (_drop_accepted, "accepted entry dropped or moved: finish_ms"),
    (_join_at_the_front, "accepted list changed, not grown at its end"),
    (_entry_among_the_accepted, "accepted entry dropped or moved"),
    (_command_changed, "accepted command changed"),
    (_layer_not_in_the_table, "is not a layer of the table"),
    (_file_disagrees, "entry disagrees with its file"),
    (_joins_a_count, "prefill_mfu_pct: asked of serve-rehearsed"),
    (_moves_what_it_does_not_report, "does not report tpot_p95_ms"),
]


@pytest.mark.parametrize("fault,refusal", FAULTS,
                         ids=[f.__name__.strip("_") for f, _ in FAULTS])
def test_the_rules_refuse_a_rehearsal_gone_wrong(fault, refusal):
    bench, extra, cell, mine = rehearse("serve")
    fault(bench, extra, cell, mine)
    with pytest.raises(AssertionError, match=refusal):
        rules.check_all(bench, extra)
