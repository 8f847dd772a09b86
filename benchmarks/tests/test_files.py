"""``BENCHMARK.json`` against the files it names: everything a cell, a
configuration or a metric needs is a file of its own, found by name."""
import json
import os
import re

import pytest

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_has_its_file(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
        assert conf["source"] == c["source"]
        assert {"assumed", "guarantees", "scale_factor"} <= set(conf)
        assert conf["connector"]["args"]["sf"] == conf["scale_factor"]
        # the guarantees the configuration states are sent, not relied on
        assert conf["session_properties"]["result_cache"] == "false"
        assert conf["session_properties"]["plan_template_cache"] == "false"
    for w in bench["workloads"]:
        with open(os.path.join(BENCH, "traffic", w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH, "templates", traffic["template"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]


def test_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", [])) <= cells
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for w in bench["workloads"]:
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    assert 2 + 14 * 24 <= (43200 - 1200 - 24 * 180) \
        // (bench["run_seconds"] + 60)


@pytest.mark.parametrize("kind", ["TPU v5 lite"])
def test_peaks_known(kind):
    import harness
    assert harness.peaks(kind)["hbm_gbytes_per_s"] == 819
    with pytest.raises(SystemExit):
        harness.peaks("TPU v9 imaginary")


def test_configuration_states_the_rows_its_data_holds(bench):
    """``scan_hbm_roofline`` counts bytes from the configuration's
    ``tables``: they are the rows the reference's data holds."""
    import harness
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        data = harness._module("", conf["reference_data"])
        sf = conf["scale_factor"]
        want = dict(data.row_counts(sf), lineitem=data.lineitem_rows(sf))
        assert conf["tables"] == {t: want[t] for t in conf["tables"]}


@pytest.mark.parametrize("key,value", [("loop", "open"), ("clients", 4),
                                       ("order", "random")])
def test_traffic_the_generator_cannot_send_is_refused(key, value):
    import harness
    traffic = {"loop": "closed", "clients": 1, "order": "in turn",
               "template": "q1", "bindings": 2}
    harness.check_traffic("ok", traffic)
    with pytest.raises(SystemExit):
        harness.check_traffic("x", dict(traffic, **{key: value}))
