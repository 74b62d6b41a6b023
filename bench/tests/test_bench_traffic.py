"""The traffic generator: seeded, open and closed loops, the same work for
every seed."""
import json

import numpy as np
import pytest

from bench.traffic import generator

OPEN = {"loop": "open", "rate_per_s": 2.5, "items": 400,
        "tenants": {"a": 0.6, "b": 0.3, "c": 0.1},
        "fields": {"prompt_len": {"dist": "lognormal", "median": 1024,
                                  "sigma": 1.0, "min": 128, "max": 2048,
                                  "int": True},
                   "output_len": {"dist": "lognormal", "median": 128,
                                  "sigma": 0.8, "min": 16, "max": 512,
                                  "int": True}}}


def test_same_seed_same_items_other_seed_other_order():
    a, b = generator.draw(OPEN, 7), generator.draw(OPEN, 7)
    assert a == b
    c = generator.draw(OPEN, 8)
    assert [it["prompt_len"] for it in a] != [it["prompt_len"] for it in c]
    assert [it["due_s"] for it in a] != [it["due_s"] for it in c]


def test_every_seed_gets_the_same_work():
    a, c = generator.draw(OPEN, 2**31 + 11), generator.draw(OPEN, 3)
    for field in ("prompt_len", "output_len", "tenant"):
        assert sorted(it[field] for it in a) == sorted(it[field] for it in c)
    assert a[-1]["due_s"] == pytest.approx(c[-1]["due_s"])


def test_open_loop_schedule():
    items = generator.draw(OPEN, 1)
    due = np.array([it["due_s"] for it in items])
    assert (np.diff(due) > 0).all()
    # 400 arrivals at 2.5/s: the last is due near 160 s
    assert due[-1] == pytest.approx(400 / 2.5, rel=0.02)
    lens = np.array([it["prompt_len"] for it in items])
    assert lens.min() >= 128 and lens.max() <= 2048
    assert np.median(lens) == pytest.approx(1024, rel=0.02)
    share = np.mean([it["tenant"] == "a" for it in items])
    assert share == pytest.approx(0.6, abs=0.01)


def test_closed_loop_has_no_due_times():
    spec = generator.load("risk-analyses")
    assert spec["loop"] == "closed"
    items = generator.draw(spec, 5)
    assert len(items) == spec["items"]
    assert all("due_s" not in it for it in items)
    scales = [it["agg_ret_scale"] for it in items]
    assert 0.5 <= min(scales) and max(scales) <= 1.5


def test_mix_files_are_checked(tmp_path):
    (tmp_path / "bad.json").write_text(json.dumps({"loop": "sideways"}))
    with pytest.raises(ValueError, match="loop"):
        generator.load("bad", tmp_path)
    (tmp_path / "mute.json").write_text(json.dumps({"loop": "closed"}))
    with pytest.raises(ValueError, match="reports"):
        generator.load("mute", tmp_path)
    with pytest.raises(FileNotFoundError):
        generator.load("absent", tmp_path)
    with pytest.raises(ValueError, match="distribution"):
        generator.quantiles({"dist": "zipfian"}, 3)
