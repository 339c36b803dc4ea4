"""BENCHMARK.json and the files it names: every cell, configuration,
driver, reference and metric is found by its name, and the file keeps
to the benchmark's contract."""
import json
import os

import pytest

import bench_smoke  # noqa: F401
import harness

SPEC = harness.benchmark_spec()
NAME_OK = set("abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
              "0123456789_.-")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_workload_loads_by_name(cell):
    w = harness.cell_spec(SPEC, cell)
    wl = harness.load_json("workloads", cell)
    assert os.path.exists(harness.BENCH / "drivers" / f"{wl['driver']}.py")
    conf = [c for c in SPEC["configs"] if c["name"] == w["config"]]
    assert len(conf) == 1
    cfg = harness.load_json("configs", w["config"])
    assert conf[0]["file"] == f"bench/configs/{w['config']}.json"
    ref = harness.reference(cfg)
    a = ref.arch(cfg)
    hash(a)
    assert a.vocab > 0 and a.bits > 0 and a.group_size
    assert w["chips"] == 1 and len(w["why"]) <= 200
    e2e = harness.cell_metrics(SPEC, cell, trace=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert harness.cell_metrics(SPEC, cell, trace=True)


def test_every_metric_has_a_reader_and_a_valid_shape():
    moved = {m["name"] for m in SPEC["end_to_end"]}
    seen = set()
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert set(m["name"]) <= NAME_OK and m["name"] not in seen
        seen.add(m["name"])
        assert m["better"] in ("lower", "higher")
        assert hasattr(harness.metric_reader(m["name"]), "read")
    for m in SPEC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert m["moves"] in moved
        for cell in m.get("workloads", []):
            assert m["moves"] in {e["name"] for e in harness.cell_metrics(
                SPEC, cell, trace=False)}
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_configs_keep_published_widths():
    for c in SPEC["configs"]:
        cfg = harness.load_json("configs", c["name"])
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) <= set(cfg)
        assert not any(k.endswith(("_dim", "_rank", "_size", "hidden_size"))
                       for k in c["reduced"])


def test_file_is_small_json():
    with open(harness.ROOT / "BENCHMARK.json") as f:
        raw = f.read()
    assert len(raw.encode()) < 64 * 1024
    json.loads(raw)


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]
                                  if harness.load_json("workloads", w["name"])
                                  ["driver"] == "serve"])
def test_warm_up_reaches_every_shape_of_the_cells_traffic(cell):
    """At the cell's own sizes: every prefill call shape of every prompt
    length, and every decode block (pow2 steps, read width) of every slot
    depth the traffic can reach, is made by some warm-up request."""
    import types

    import traffic
    from repro.serve.engine import ContinuousEngine
    from repro.serve.kvcache import default_page_spec

    serve = harness.driver("serve")
    e = harness.load_json("workloads", cell)["engine"]
    lens = traffic.length_range(harness.load_json("workloads", cell)
                                ["traffic"])
    eng = types.SimpleNamespace(
        spec=default_page_spec(e["n_slots"], lens[1] + lens[3],
                               e["page_size"]),
        prefill_bucket=e["prefill_bucket"], decode_block=e["decode_block"],
        chunk_tokens=(e["chunked_prefill"] // e["page_size"]
                      * e["page_size"]))
    eng._bucket = lambda n: ContinuousEngine._bucket(eng, n)
    eng._read_width = lambda n: ContinuousEngine._read_width(eng, n)
    reqs = serve.warm_requests(eng, lens)
    made_pre, made_dec = set(), set()
    for p, n in reqs:
        assert lens[0] <= p <= lens[1] and 1 <= n <= lens[3]
        made_pre |= set(serve._chunks(eng, p))
        made_dec |= serve._decode_blocks(eng, p, n)
    for n in range(lens[0], lens[1] + 1):
        assert set(serve._chunks(eng, n)) <= made_pre
    ks = [1 << i for i in range(e["decode_block"].bit_length())]
    for k in ks:
        for c in range(lens[0], lens[1] + lens[3] - k + 1):
            assert (k, eng._read_width(c + k)) in made_dec


def test_a_reference_module_without_the_contract_is_refused(monkeypatch):
    cfg = harness.load_json("configs", SPEC["configs"][0]["name"])
    monkeypatch.delattr(harness.reference(cfg), "paged_reads")
    with pytest.raises(SystemExit, match="paged_reads"):
        harness.reference(cfg)
