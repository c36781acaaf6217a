"""The cell ``github-org-live.ycsb-b`` (PR 47): its entries in the manifest and
what they leave alone, its files, its thin driver, each of its readers over two
recorded ``/metrics`` scrapes of a CPU rehearsal (200,000 tuples, 10 s, two
folds: counts and parity, not device numbers) and over the same scrapes as the
parent would answer them, the controls of ``correct``, and the CPU rehearsal at
200,000 tuples, where the fold's sorts pass the device threshold. Nothing here
is a device number."""

import json
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT))

from benchmarks import control_writes  # noqa: E402
from benchmarks import manifest as manifest_mod  # noqa: E402
from benchmarks import run, traffic  # noqa: E402
from benchmarks.daemon import Metrics  # noqa: E402
from benchmarks.drivers import readwrite, readwrite_reads  # noqa: E402
from benchmarks.drivers.open_single import MISS_MS  # noqa: E402

CELL, CONFIG, MIX = "github-org-live.ycsb-b", "github-org-live", "ycsb-b"
PROPOSAL = str(ROOT / "benchmarks" / "proposed" / "github-org.readwrite.json")
MANIFEST = manifest_mod.load()
FROM_THE_DRIVER = {"writer_visible_p50_ms.ycsb-b", "writer_visible_p95_ms.ycsb-b", "read_p95_ms.ycsb-b",
                   "write_ack_ms.ycsb-b", "pinned_wait_ms.ycsb-b"}
FROM_OLDER_FAMILIES = {"folds.ycsb-b", "fold_s.ycsb-b", "overlay_edges.ycsb-b", "window_compile_s.ycsb-b",
                       "long_stay_ms.ycsb-b", "decline_share.ycsb-b", "label_route_share.ycsb-b",
                       "group_commit_wait_ms.ycsb-b"}
FROM_THIS_PR = {"fold_sort_host_share.ycsb-b", "lock_wait_ms_per_kcheck.ycsb-b", "stale_serves.ycsb-b"}
NEW_LAYERS = FROM_THE_DRIVER | FROM_OLDER_FAMILIES | FROM_THIS_PR
SET_UP = {"snapshot_build_s", "warmup_s", "boot_compile_s"}


# -- the manifest: added to, and two kinds of list -------------------------------


def _entry(kind: str, name: str) -> dict:
    """By name, not by place: later PRs append to every list."""
    (entry,) = [e for e in MANIFEST[kind] if e["name"] == name]
    return entry


def test_the_manifest_holds_the_deployment_and_its_one_cell():
    config, cell = _entry("configs", CONFIG), _entry("workloads", CELL)
    assert [w["name"] for w in MANIFEST["workloads"] if w["config"] == CONFIG] == [CELL]
    assert config["reduced"] == ["tuples"]
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json" and len(config["source"]) <= 200
    assert "YCSB core workload B" in config["source"] and "Zanzibar" in config["source"]
    assert cell == {"name": CELL, "config": CONFIG, "traffic": MIX, "chips": 1, "why": cell["why"]}
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200


def test_the_cell_reports_check_p50_ms_and_not_the_tail():
    c = run.Cell(CELL)
    reported = {m["name"] for m in c.e2e}
    assert {"check_p50_ms", "setup_s"} <= reported and "check_p95_ms" not in reported
    assert CELL in _entry("end_to_end", "check_p50_ms")["workloads"]
    assert CELL not in _entry("end_to_end", "check_p95_ms")["workloads"]
    assert set(c.readers) >= NEW_LAYERS | SET_UP and len(NEW_LAYERS) == 16


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_each_new_entry_is_the_cells_alone_and_moves_what_it_reports(name):
    entry = _entry("per_layer", name)
    assert entry["workloads"] == [CELL] and entry["moves"] == "check_p50_ms"
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert entry["source"] == ("host_clock" if name in FROM_THE_DRIVER else
                               "program_span" if name.startswith("group_commit_wait") else
                               "program_counter")
    assert callable(run.load_module("layers", name).read)


@pytest.mark.parametrize("name", ["serve_overhead_ms.interactive", "slice_ms.interactive",
                                  "pool_wait_ms.interactive"])
def test_the_interactive_readers_that_move_check_p50_ms_stay_the_interactive_cells(name):
    entry = _entry("per_layer", name)
    assert "rbac-groups.interactive" in entry["workloads"] and CELL not in entry["workloads"]
    assert entry["moves"] == "check_p50_ms"
    assert name not in run.Cell(CELL).readers and name in run.Cell("rbac-groups.interactive").readers


@pytest.mark.parametrize("older", ["rbac-groups.interactive", "github-org.bulk",
                                   "github-blocklist.bulk"])
def test_an_older_cell_reports_nothing_of_this_cells(older):
    readers = set(run.Cell(older).readers)
    assert not readers & NEW_LAYERS and SET_UP <= readers
    # what it moved end to end, it still has a per-layer reader for
    assert {"rbac-groups.interactive": "serve_overhead_ms.interactive",
            "github-org.bulk": "serve_overhead_ms.bulk",
            "github-blocklist.bulk": "gate_check_share.bulk"}[older] in readers


def test_the_proposed_cell_still_loads_beside_it():
    c = run.Cell("github-org.readwrite", propose=PROPOSAL)
    assert {m["name"] for m in c.e2e} == {"write_visible_p50_ms", "write_visible_p95_ms", "setup_s"}
    assert not [n for n in c.readers if n.endswith(".ycsb-b")]
    assert set(run.Cell(CELL, propose=PROPOSAL).readers) == set(run.Cell(CELL).readers)


# -- the files: the mix to the letter, the guarantees made the configuration's own ---


def test_the_mix_is_readwrites_to_the_letter_but_for_its_driver_and_its_rate():
    mix, accepted = traffic.load_mix(MIX), traffic.load_mix("readwrite")
    assert {k for k in mix if mix[k] != accepted[k]} == {"driver", "rate"} and set(mix) == set(accepted)
    assert mix["driver"] == "readwrite_reads" and mix["rate"] == {CONFIG: 500}
    assert mix["writes_per_read"] == [5, 95] and mix["skew"] == {"kind": "zipf", "theta": 0.99}
    assert mix["write_kinds"] == {"join": 50, "leave": 20, "file": 20, "grant": 10}
    ctx = SimpleNamespace(mix=mix, config_name=CONFIG)
    assert readwrite.rates_of(ctx) == (500.0, 500.0 * 5 / 95)


def test_the_configuration_is_github_orgs_store_under_guarantees_of_its_own():
    live = json.loads((ROOT / "benchmarks/configs/github-org-live.json").read_text())
    org = json.loads((ROOT / "benchmarks/configs/github-org.json").read_text())
    for key in ("generator", "tuples", "source_tuples", "reduced", "reduced_why", "namespaces",
                "control_max_depth", "serve"):
        assert live[key] == org[key], key
    assert live["serve"] == {} and "reference" not in live  # the daemon's defaults; reference.py
    assert live["assumed"] == org["assumed"] + traffic.load_mix(MIX)["assumed"]
    ours, theirs = live["guarantees"], org["guarantees"]
    assert set(ours) == {"answers", "store", "consistency", "durability"}
    assert ours["durability"] == theirs["durability_readwrite"]
    assert ours["consistency"] == theirs["consistency_readwrite"][len("under readwrite "):]
    assert ours["consistency"].startswith("the store is written to inside the run, and: a check pinned")
    assert live["source"] == _entry("configs", CONFIG)["source"]


# -- the driver: readwrite's run, reported by its reads ---------------------------


def _played(seed: int, stale: bool):
    cell = run.Cell(CELL, tuples=20_000)
    ctx = cell.inputs(seed, 4.0)
    state = cell.driver.prepare(ctx)
    return cell, state, control_writes.play(cell, ctx, state, stale=stale)


def test_the_driver_adds_nothing_but_the_choice_of_metric():
    assert readwrite_reads.prepare is readwrite.prepare and readwrite_reads.warm is readwrite.warm
    _, state, ours = _played(7, stale=False)
    theirs = dict(ours, metrics={k: ours[k] for k in ("write_visible_p50_ms", "write_visible_p95_ms")})
    assert ours["metrics"] == {"check_p50_ms": ours["extras"]["read_p50_ms"]}
    assert 0 < ours["metrics"]["check_p50_ms"] < MISS_MS and ours["wrong"] == ours["failed"] == 0
    # everything else is the accepted driver's result, key for key
    again = readwrite.reduce(state, state["window"], *_records(state, stale=False))
    assert again["metrics"] == theirs["metrics"]
    # (the writer's two values besides ride among the extras, for an untraced run's window line)
    assert {k: ours["extras"].pop(k) for k in theirs["metrics"]} == theirs["metrics"]
    assert {k: v for k, v in ours.items() if k not in ("metrics", *theirs["metrics"])} == \
           {k: v for k, v in again.items() if k != "metrics"}


def _records(state, stale: bool):
    """The window's records as ``control_writes.play`` makes them, kept."""
    kept = {}
    cell = run.Cell(CELL, tuples=20_000)
    driver = SimpleNamespace(_log_of=readwrite._log_of,
                             reduce=lambda s, phase, reads, writes: kept.update(r=(reads, writes)) or {})
    fake = SimpleNamespace(reference=cell.reference, driver=driver)
    control_writes.play(fake, cell.inputs(7, 4.0), state, stale=stale)
    return kept["r"]


def test_a_read_that_missed_is_charged_as_open_single_charges_it():
    """More than half the window's reads failed: the median is ``MISS_MS``."""
    _, state, _ = _played(7, stale=False)
    reads, writes = _records(state, stale=False)
    failed = [(i, due, sent, replied, 504 if n % 3 else status, served)
              for n, (i, due, sent, replied, status, served) in enumerate(reads)]
    out = readwrite_reads.reduce(state, state["window"], failed, writes)
    assert out["metrics"] == {"check_p50_ms": MISS_MS} and out["failed"] >= len(reads) // 2


@pytest.mark.parametrize("seed", [11, 12])
def test_the_server_that_applies_no_write_is_caught_on_this_cell(seed):
    out = control_writes.control_run(run.Cell(CELL, tuples=20_000), seed, 4.0)
    assert out["sound_vs_analytic"] == 0 and out["sound_vs_judge"] == 0
    assert out["control_vs_analytic"] > 0 and out["control_vs_judge"] > 0
    assert out["control_join_follow_ups_counted"] == out["join_follow_ups"] > 0
    assert out["blind_judge_join_follow_ups_counted"] == out["join_follow_ups"]


# -- the readers, over a recorded window ------------------------------------------

#: what each reader gives on the recorded window, reckoned by hand from the two
#: files (5,526 correct checks delivered; the fold inside it sorted one batch of
#: 262,144 on the device and four small ones in numpy)
RECORDED = {
    "folds.ycsb-b": 1.0,
    "fold_s.ycsb-b": 0.3736904120014515 - 0.2115752809913829,
    "overlay_edges.ycsb-b": 114.0,
    "window_compile_s.ycsb-b": 0.0,
    "long_stay_ms.ycsb-b": 1e3 * (0.13263332200585864 + 0.07301423401804641
                                  + 0.567204886028776 - 0.3061823090247344),
    "decline_share.ycsb-b": 100.0 * (1265 + 444) / (855 + 444 + 58 + 1265),
    "label_route_share.ycsb-b": 100.0 * 1319 / 1323,
    "group_commit_wait_ms.ycsb-b": 1e3 * (1.5738599791075103 - 0.42105568014085293) / 263,
    "fold_sort_host_share.ycsb-b": 0.0,
    "lock_wait_ms_per_kcheck.ycsb-b": 0.14229913699091412 * 1e3 / 5.526,
    "stale_serves.ycsb-b": 0.0,
    "writer_visible_p50_ms.ycsb-b": 26.5, "writer_visible_p95_ms.ycsb-b": 91.0,
    "read_p95_ms.ycsb-b": 66.0, "write_ack_ms.ycsb-b": 11.5, "pinned_wait_ms.ycsb-b": 13.25,
}
#: the families a program before PR 47 does not scrape
NEW_FAMILIES = ("keto_build_sort_", "keto_engine_lock_wait_seconds_total",
                "keto_snapshot_stale_serves_total")


def recorded_run(as_the_parent: bool = False, edit=lambda text: text):
    def scrape(name):
        text = (HERE / f"recorded_ycsb_scrape_{name}.txt").read_text()
        if as_the_parent:
            text = "\n".join(ln for ln in text.splitlines() if not ln.startswith(NEW_FAMILIES))
        return Metrics(edit(text) if name == "after" else text)

    return SimpleNamespace(
        before=scrape("before"), after=scrape("after"), trace=None,
        result={"attempted": 5526, "failed": 0, "wrong": 0, "write_visible_p50_ms": 26.5,
                "write_visible_p95_ms": 91.0, "write_ack_ms_median": 11.5,
                "pinned_wait_ms_median": 13.25, "extras": {"read_p95_ms": 66.0}})


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_a_reader_reads_the_recorded_window(name):
    assert set(RECORDED) == NEW_LAYERS
    assert run.load_module("layers", name).read(recorded_run()) == pytest.approx(RECORDED[name], rel=1e-9)


@pytest.mark.parametrize("name", sorted(NEW_LAYERS))
def test_on_the_parent_a_reader_of_this_prs_counters_returns_nothing_and_does_not_raise(name):
    got = run.load_module("layers", name).read(recorded_run(as_the_parent=True))
    if name in FROM_THIS_PR:
        assert got is None
    else:
        assert got == pytest.approx(RECORDED[name], rel=1e-9)


@pytest.mark.parametrize("why, share", [("cold", 50.0), ("pressure", 50.0), ("error", 50.0),
                                        ("small", 0.0)])
def test_the_host_share_counts_the_batches_that_were_large_enough_for_the_device(why, share):
    """One more host batch in the window beside the fold's one on the device."""
    line = f'keto_build_sort_total{{backend="host",why="{why}"}} '
    bump = lambda text: "\n".join(
        line + str(float(ln.split()[-1]) + 1) if ln.startswith(line) else ln
        for ln in text.splitlines())
    got = run.load_module("layers", "fold_sort_host_share.ycsb-b").read(recorded_run(edit=bump))
    assert got == pytest.approx(share)


# -- the CPU rehearsal, whole, at 200,000 tuples ----------------------------------


def rehearse(trace: int, seed: int, seconds: int = 10):
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmarks" / "run.py"), "--workload", CELL, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace), "--platform", "cpu", "--tuples", "200000"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout.strip().splitlines(), proc.stderr


def test_the_rehearsal_is_correct_under_0_0_0_and_reports_the_reads():
    lines, err = rehearse(0, 3_000_000_047)
    line = json.loads(lines[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["unjudged"] == 0
    assert line["compared"] == {k: {"value": 0, "limit": 0} for k in
                                ("differ_from_analytic", "differ_from_reference", "served_before_pin")}
    assert "served_before_pin = 0 (limit 0)" in err
    assert {"check_p50_ms", "setup_s"} <= set(line["metrics"])
    assert 0 < line["metrics"]["check_p50_ms"]["value"] < 1e6
    (window,) = [ln for ln in lines if "] window: " in ln]
    extras = json.loads(window.split("] window: ", 1)[1].split("; compile cache", 1)[0])
    assert extras["reads"] == 5_000 and extras["writes"] == 263 and extras["wrong_answers"] == []
    assert set(extras["writes_by_kind"]) == {"join", "leave", "file", "grant"}
    assert line["metrics"]["check_p50_ms"]["value"] == extras["read_p50_ms"]


def test_the_traced_rehearsal_folds_without_a_compile_and_says_who_waited():
    lines, _ = rehearse(1, 47)
    line = json.loads(lines[-1])
    assert line["correct"] is True and set(line["metrics"]) >= NEW_LAYERS | SET_UP
    value = lambda name: line["metrics"][name]["value"]
    assert value("folds.ycsb-b") >= 1 and 0 < value("fold_s.ycsb-b") < 5
    assert value("window_compile_s.ycsb-b") == 0 and value("stale_serves.ycsb-b") == 0
    assert value("fold_sort_host_share.ycsb-b") == 0  # the boot build compiled the fold's rung
    assert value("lock_wait_ms_per_kcheck.ycsb-b") > 0  # a read met the fold and waited for it
    assert line["device"]["busy_s"] > 0


@pytest.mark.parametrize("entry, least", [("stale_entry.py", 30), ("broken_entry.py", 1)])
def test_a_broken_daemon_comes_out_not_correct(entry, least):
    """The rest of a run, past the look for a chip, on a daemon that never
    refreshes its snapshot (every follow-up of a join is denied, under its
    pin) and on one that inverts every 997th decision."""
    args = SimpleNamespace(workload=CELL, seed=79, seconds=3.0, trace=0, platform="cpu",
                           tuples=20_000, keep_log="", propose="")
    line = run.execute(args, lambda msg: None, entry=HERE / entry)
    assert line["correct"] is False and line["device"]["platform"] == "cpu"
    assert line["compared"]["differ_from_analytic"]["value"] >= least
    if entry == "stale_entry.py":
        assert line["compared"]["differ_from_reference"]["value"] >= least
        assert line["compared"]["served_before_pin"]["value"] >= least
