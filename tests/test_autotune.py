"""Unit tests for kernels/autotune.py: the deterministic fallback table,
shape-class bucketing, JSON cache hygiene (version hash, corrupt files,
invalid entries), and the measured REPRO_AUTOTUNE=1 search."""
import json

import pytest

from repro.core.quant.types import pack_layout
from repro.kernels import autotune, template


@pytest.fixture(autouse=True)
def _fresh_cache_state():
    """Each test starts and ends with a cold in-memory cache."""
    autotune.reset()
    yield
    autotune.reset()


# ------------------------------------------------ deterministic fallback

def test_cold_cache_resolution_is_the_fallback_table(monkeypatch):
    """Default mode with no cache file resolves every shape class from the
    deterministic table — and does so identically on repeat calls (the
    replay-twice / sanitizer contract)."""
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    shapes = [(1, 128, 256, 4, 32), (64, 256, 512, 2, -1),
              (9, 128, 128, 8, 64)]
    for kind in ("dequant", "w8a8", "grouped_expert", "grouped_w8a8"):
        for m, k, n, bits, gs in shapes:
            want = autotune.fallback_matmul_plan(
                m, k, n, kind=kind, bits=bits, group_size=gs, bm=128,
                bn=256, bk=256)
            got = autotune.matmul_plan(kind, m, k, n, bits=bits,
                                       group_size=gs)
            assert got == want
            assert autotune.matmul_plan(kind, m, k, n, bits=bits,
                                        group_size=gs) == got
    assert autotune.paged_tile(16, "bf16", 1) == 16
    assert autotune.paged_tile(512, "int8", 4) == 256


def test_mode_zero_ignores_a_warm_cache(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE=0 pins the table even when a valid warm cache entry
    exists (CI / deterministic replay)."""
    path = str(tmp_path / "tune.json")
    key = autotune.matmul_key("dequant", 4, 128, 256, 4, 32)
    autotune.save_cache(path, {key: {"bm": 8, "bn": 128, "bk": 64}})
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    monkeypatch.setenv("REPRO_AUTOTUNE", "0")
    want = autotune.fallback_matmul_plan(4, 128, 256, kind="dequant", bits=4,
                                         group_size=32, bm=128, bn=256,
                                         bk=256)
    assert autotune.matmul_plan("dequant", 4, 128, 256, bits=4,
                                group_size=32) == want


def test_pick_bk_per_channel_fast_path():
    """group_size == K (per-channel) takes the direct largest-divisor path:
    the halving loop could only ever return K itself (regression: W4 g=-1
    at K=1012 ran one whole-K block instead of 11 x 92-row blocks)."""
    assert autotune.pick_bk(1012, 1012, 4, 256) == 92
    assert autotune.pick_bk(128, 128, 4, 256) == 128
    assert autotune.pick_bk(24, 24, 8, 256) == 24
    # K not a multiple of the byte group can never pack
    assert autotune.pick_bk(1012, 1012, 8, 256) is None
    # no >= 8-row divisor under the target: one whole-K block
    assert autotune.pick_bk(1012, 1012, 4, 8) == 1012


# ------------------------------------------ row-tile plans at cell widths

# (projection, bits, K, N, most grid steps one call may take) of every
# quantized projection in a block of the benchmark's two configurations:
# ChatGLM3-6B at W4 g128 (d 4096, 2 KV heads of 128, d_ff 13696 =
# 128 * 107) and Granite-20B at W3 g128 (d 6144, one KV head, d_ff 24576)
GLM_W4 = [("q", 4, 4096, 4096, 32), ("k", 4, 4096, 256, 2),
          ("v", 4, 4096, 256, 2), ("o", 4, 4096, 4096, 32),
          ("gate", 4, 4096, 13696, 107), ("up", 4, 4096, 13696, 107),
          ("down", 4, 13696, 4096, 107)]
GRANITE_W3 = [("q", 3, 6144, 6144, 72), ("k", 3, 6144, 128, 2),
              ("v", 3, 6144, 128, 2), ("o", 3, 6144, 6144, 72),
              ("up", 3, 6144, 24576, 288), ("down", 3, 24576, 6144, 288)]
CELL_PROJECTIONS = ([("glm",) + p for p in GLM_W4] +
                    [("granite",) + p for p in GRANITE_W3])
# dequant grid steps of one block at one row tile (the table gave 5680
# and 5808 before the row-tile regime)
MAX_STEPS_PER_LAYER = {"glm": 389, "granite": 724}


def _assert_legal(plan, m, k, n, bits, gs):
    bm, bn, bk = plan
    bpg, vpg = pack_layout(bits)
    assert bm >= m and bm % 8 == 0
    assert n % bn == 0 and (bn % 128 == 0 or bn == n)
    assert k % bk == 0 and (bk % 128 == 0 or bk == k)
    assert bk % vpg == 0 and (bk // vpg * bpg % 32 == 0 or bk == k)
    assert (gs >= bk and gs % bk == 0) or (gs < bk and bk % gs == 0)


@pytest.mark.parametrize("m", [64, 128])
@pytest.mark.parametrize("cell,proj,bits,k,n,max_steps", CELL_PROJECTIONS,
                         ids=[f"{c[0]}-{c[1]}" for c in CELL_PROJECTIONS])
def test_row_tile_plan_at_cell_projections(m, cell, proj, bits, k, n,
                                           max_steps):
    """A 64-slot decode call, and a 128-row call, at every projection of
    the benchmark cells takes the row-tile regime: one row tile, a legal
    weight block within the budget, and at most the stated grid steps."""
    plan = autotune.matmul_plan("dequant", m, k, n, bits=bits,
                                group_size=128)
    _assert_legal(plan, m, k, n, bits, 128)
    bm, bn, bk = plan
    assert bm == m
    assert bk * bn <= autotune._DEQUANT_BLOCK_VALUES
    rec = autotune.issued_plans()[
        autotune.matmul_key("dequant", m, k, n, bits, 128)]
    assert rec == {"bm": bm, "bn": bn, "bk": bk, "regime": "row_tile",
                   "grid_steps": (n // bn) * (k // bk)}
    assert rec["grid_steps"] <= max_steps


@pytest.mark.parametrize("cell,projs", [("glm", GLM_W4),
                                        ("granite", GRANITE_W3)])
def test_row_tile_grid_steps_per_layer(cell, projs):
    """A decode step's dequant grid steps in one block, from the plans the
    table issued (read back through `issued_plans`)."""
    steps = 0
    for _, bits, k, n, _ in projs:
        autotune.matmul_plan("dequant", 64, k, n, bits=bits, group_size=128)
        steps += autotune.issued_plans()[autotune.matmul_key(
            "dequant", 64, k, n, bits, 128)]["grid_steps"]
    assert steps <= MAX_STEPS_PER_LAYER[cell]


def test_row_tile_searches_divisors_not_halvings():
    """K = 128 * 107 (ChatGLM3's d_ff): halving from 256 lands on 128 for
    both axes; the divisor search reaches the whole 4096-wide N block and
    the whole 4096-deep K block of the transposed shape, and of equal
    sizes takes the wider N block."""
    assert autotune.row_tile_plan(64, 13696, 4096, bits=4,
                                  group_size=128) == (64, 4096, 128)
    assert autotune.row_tile_plan(64, 4096, 13696, bits=4,
                                  group_size=128) == (64, 128, 4096)
    assert autotune.row_tile_plan(64, 4096, 4096, bits=4,
                                  group_size=128) == (64, 4096, 128)
    # bm is the token count padded to whole 8-row sublanes
    assert autotune.row_tile_plan(1, 256, 128, bits=4,
                                  group_size=64)[0] == 8
    assert autotune.row_tile_plan(100, 256, 128, bits=4,
                                  group_size=64)[0] == 104
    # W3: a 128-value K block is 48 packed rows, not whole 32-row uint8
    # tiles, so the widest block W4 takes at this shape is not legal
    assert autotune.row_tile_plan(64, 6144, 4096, bits=3,
                                  group_size=128) == (64, 2048, 256)
    assert autotune.row_tile_plan(64, 6144, 4096, bits=4,
                                  group_size=128) == (64, 4096, 128)
    # only whole-dim blocks are legal and they exceed the budget: no
    # row-tile plan, and the table's token-count regime takes the call
    assert autotune.row_tile_plan(64, 8008, 200, bits=4,
                                  group_size=-1) is None
    assert autotune.matmul_plan("dequant", 64, 8008, 200, bits=4,
                                group_size=-1) == (64, 200, 182)
    assert autotune.issued_plans()[autotune.matmul_key(
        "dequant", 64, 8008, 200, 4, -1)]["regime"] == "prefill"


# plans of the other kinds and of M > 128, as the table gave them before
# the row-tile regime existed
UNCHANGED = [
    ("w8a8", 64, 4096, 13696, 4, (64, 128, 256)),
    ("w8a8", 8, 4096, 4096, 4, (8, 512, 512)),
    ("w8a8", 64, 13696, 4096, 4, (64, 256, 128)),
    ("w8a8", 128, 6144, 24576, 3, (128, 256, 256)),
    ("dequant", 256, 4096, 13696, 4, (128, 128, 256)),
    ("dequant", 512, 13696, 4096, 4, (128, 256, 128)),
    ("dequant", 256, 6144, 24576, 3, (128, 256, 256)),
]


@pytest.mark.parametrize("kind,m,k,n,bits,want", UNCHANGED)
def test_other_kinds_and_large_m_keep_the_table(kind, m, k, n, bits, want):
    assert autotune.matmul_plan(kind, m, k, n, bits=bits,
                                group_size=128) == want
    rec = autotune.issued_plans()[autotune.matmul_key(kind, m, k, n, bits,
                                                      128)]
    assert rec["regime"] == ("skinny" if m <= 8 else "prefill")


@pytest.mark.parametrize("n,want", [
    (4096, 256), (13696, 128),    # lane tiles up to the target
    (576, 576), (80, 80),         # no lane tile fits: N whole
])
def test_table_n_blocks_are_lane_tiles(n, want):
    """A table plan's N block is a multiple of 128 that divides N, or N
    whole: halving 576 would give a 64-lane block Mosaic refuses."""
    assert autotune.lane_block(n, 256) == want
    assert autotune.fallback_matmul_plan(
        1024, 2048, n, kind="dequant", bits=4, group_size=128, bm=128,
        bn=256, bk=256)[1] == want


@pytest.mark.parametrize("rows,experts,want", [
    (384, 64, 16),      # 64 decode slots, top-6 of 64 experts
    (768, 64, 32),      # a 128-token prefill
    (6144, 64, 128),    # a 1024-token prefill: capped at one row tile
    (8, 4, 16),         # the minimal tile
])
def test_grouped_row_tile_holds_an_expert_in_one_tile(rows, experts, want):
    assert autotune.grouped_row_tile(rows, experts) == want


def test_grouped_kinds_take_the_row_tile_plan():
    """The grouped expert kinds walk one row tile per call step: their
    weight blocks are the row-tile plan's (DeepSeek-V2-Lite's expert
    widths at 16-row tiles, W4 g128)."""
    for kind in ("grouped_expert", "grouped_w8a8"):
        assert autotune.matmul_plan(kind, 16, 2048, 1408, bits=4,
                                    group_size=128) == (16, 1408, 256)
        assert autotune.matmul_plan(kind, 16, 1408, 2048, bits=4,
                                    group_size=128) == (16, 256, 1408)
        rec = autotune.issued_plans()[autotune.matmul_key(
            kind, 16, 1408, 2048, 4, 128)]
        assert rec["regime"] == "row_tile" and rec["grid_steps"] == 8


# ------------------------------------------------------- shape-class keys

def test_m_bucket_collapses_decode_and_pow2():
    assert [autotune.m_bucket(m) for m in (1, 3, 8)] == [8, 8, 8]
    assert autotune.m_bucket(9) == 16
    assert autotune.m_bucket(16) == 16
    assert autotune.m_bucket(17) == 32


def test_shape_class_keys():
    k1 = autotune.matmul_key("dequant", 1, 256, 512, 4, 32)
    k8 = autotune.matmul_key("dequant", 8, 256, 512, 4, 32)
    k9 = autotune.matmul_key("dequant", 9, 256, 512, 4, 32)
    assert k1 == k8 and k8 != k9                       # decode class
    assert autotune.matmul_key("w8a8", 1, 256, 512, 4, 32) != k1
    assert (autotune.paged_key(16, "bf16", 1)
            != autotune.paged_key(16, "int8", 1))
    assert (autotune.paged_key(16, "bf16", 1)
            == autotune.paged_key(16, "bf16", 8))      # m-rows bucket


# ------------------------------------------------------------ cache files

def test_cache_round_trip(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    key = autotune.matmul_key("dequant", 4, 128, 256, 4, 32)
    autotune.save_cache(path, {key: {"bm": 8, "bn": 128, "bk": 64}})
    assert autotune.load_cache(path) == {key: {"bm": 8, "bn": 128, "bk": 64}}
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    assert autotune.matmul_plan("dequant", 4, 128, 256, bits=4,
                                group_size=32) == (8, 128, 64)
    # shape classes not in the cache still resolve from the table
    want = autotune.fallback_matmul_plan(4, 128, 256, kind="dequant", bits=4,
                                         group_size=64, bm=128, bn=256,
                                         bk=256)
    assert autotune.matmul_plan("dequant", 4, 128, 256, bits=4,
                                group_size=64) == want


def test_missing_cache_file_is_cold(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "absent.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    want = autotune.fallback_matmul_plan(1, 128, 256, kind="dequant", bits=4,
                                         group_size=32, bm=128, bn=256,
                                         bk=256)
    assert autotune.matmul_plan("dequant", 1, 128, 256, bits=4,
                                group_size=32) == want


def test_stale_template_version_is_ignored(tmp_path, caplog):
    path = str(tmp_path / "tune.json")
    key = autotune.matmul_key("dequant", 4, 128, 256, 4, 32)
    payload = {"version": "0" * 16,
               "entries": {key: {"bm": 8, "bn": 128, "bk": 64}}}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(payload, f)
    with caplog.at_level("WARNING"):
        assert autotune.load_cache(path) == {}
    assert "template" in caplog.text


@pytest.mark.parametrize("content", ["{not json", '["a", "list"]',
                                     '{"version": "x"}'])
def test_corrupt_cache_falls_back(tmp_path, caplog, content, monkeypatch):
    """Corrupt / wrong-shape cache files warn and hand over to the table —
    never an exception on the serving path."""
    path = str(tmp_path / "tune.json")
    with open(path, "w", encoding="utf-8") as f:
        f.write(content)
    with caplog.at_level("WARNING"):
        assert autotune.load_cache(path) == {}
    assert "unreadable" in caplog.text or "template" in caplog.text
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    want = autotune.fallback_matmul_plan(4, 128, 256, kind="dequant", bits=4,
                                         group_size=32, bm=128, bn=256,
                                         bk=256)
    assert autotune.matmul_plan("dequant", 4, 128, 256, bits=4,
                                group_size=32) == want


def test_invalid_cached_entry_is_revalidated_away(tmp_path, monkeypatch,
                                                  caplog):
    """A hand-edited or stale entry that violates the tiling constraints
    can never reach pallas_call: it is dropped with a warning."""
    path = str(tmp_path / "tune.json")
    mk = autotune.matmul_key("dequant", 4, 128, 256, 4, 32)
    pk = autotune.paged_key(16, "bf16", 1)
    autotune.save_cache(path, {mk: {"bm": 8, "bn": 100, "bk": 64},
                               pk: {"tile": 13}})
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    want = autotune.fallback_matmul_plan(4, 128, 256, kind="dequant", bits=4,
                                         group_size=32, bm=128, bn=256,
                                         bk=256)
    with caplog.at_level("WARNING"):
        assert autotune.matmul_plan("dequant", 4, 128, 256, bits=4,
                                    group_size=32) == want
        assert autotune.paged_tile(16, "bf16", 1) == 16
    assert "violates" in caplog.text


# ------------------------------------------------------- measured search

def test_measured_mode_persists_and_reuses(tmp_path, monkeypatch):
    """REPRO_AUTOTUNE=1 measures real pallas_call candidates, persists the
    winner under the current template version, and the default mode then
    serves it warm."""
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    plan = autotune.matmul_plan("dequant", 4, 128, 128, bits=4,
                                group_size=32)
    assert plan is not None
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    assert data["version"] == template.TEMPLATE_VERSION
    key = autotune.matmul_key("dequant", 4, 128, 128, 4, 32)
    assert data["entries"][key] == {"bm": plan[0], "bn": plan[1],
                                    "bk": plan[2]}
    monkeypatch.setenv("REPRO_AUTOTUNE", "")
    autotune.reset()
    assert autotune.matmul_plan("dequant", 4, 128, 128, bits=4,
                                group_size=32) == plan


def test_measured_mode_untileable_shape_returns_none(monkeypatch):
    """No candidate lowers (K=18, gs=2): the search degrades to the
    fallback, which is None — callers take the jnp reference."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune.matmul_plan("dequant", 4, 18, 16, bits=2,
                                group_size=2) is None


def test_measured_paged_tile(tmp_path, monkeypatch):
    path = str(tmp_path / "tune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE", "1")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    tile = autotune.paged_tile(128, "bf16", 1)
    assert tile in (64, 128)
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    assert data["entries"][autotune.paged_key(128, "bf16", 1)] == \
        {"tile": tile}
