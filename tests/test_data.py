"""Data library tests (models the reference's data test strategy:
block-level asserts + end-to-end results, python/ray/data/tests/)."""

import os

import numpy as np
import pytest

import ray_tpu
from ray_tpu import data as rd


@pytest.fixture(scope="module", autouse=True)
def rt():
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield
    ray_tpu.shutdown()


def test_range_count_take():
    ds = rd.range(1000)
    assert ds.count() == 1000
    rows = ds.take(3)
    assert rows == [{"id": 0}, {"id": 1}, {"id": 2}]


def test_map_batches_streaming():
    ds = rd.range(100).map_batches(lambda b: {"id": b["id"] * 2})
    assert ds.sum("id") == 2 * sum(range(100))


def test_map_filter_flat_map():
    ds = rd.range(10).map(lambda r: {"id": r["id"] + 1})
    ds = ds.filter(lambda r: r["id"] % 2 == 0)
    ds = ds.flat_map(lambda r: [{"id": r["id"]}, {"id": -r["id"]}])
    vals = sorted(r["id"] for r in ds.take_all())
    assert vals == sorted([2, -2, 4, -4, 6, -6, 8, -8, 10, -10])


def test_fused_chain_is_single_stage():
    ds = rd.range(64).map_batches(lambda b: b).map_batches(lambda b: b)
    ds.take_all()
    stats = ds.stats()
    assert "Range+" in stats  # read fused with downstream maps


def test_batch_iteration_and_shapes():
    ds = rd.range(256)
    batches = list(ds.iter_batches(batch_size=100, drop_last=False))
    sizes = [len(b["id"]) for b in batches]
    assert sizes == [100, 100, 56]
    batches = list(ds.iter_batches(batch_size=100, drop_last=True))
    assert [len(b["id"]) for b in batches] == [100, 100]


def test_local_shuffle_and_seed():
    ds = rd.range(64)
    a = list(ds.iter_batches(batch_size=64, local_shuffle_buffer_size=64,
                             local_shuffle_seed=0))[0]["id"]
    b = list(ds.iter_batches(batch_size=64, local_shuffle_buffer_size=64,
                             local_shuffle_seed=0))[0]["id"]
    assert not np.array_equal(a, np.arange(64))
    assert np.array_equal(a, b)


def test_repartition_and_shuffle_preserve_rows():
    ds = rd.range(500).repartition(5)
    assert ds.count() == 500
    shuffled = rd.range(500).random_shuffle(seed=42)
    vals = np.sort(np.asarray([r["id"] for r in shuffled.take_all()]))
    assert np.array_equal(vals, np.arange(500))


def test_sort():
    ds = rd.from_items([{"x": int(v)} for v in [5, 3, 9, 1, 7]])
    assert [r["x"] for r in ds.sort("x").take_all()] == [1, 3, 5, 7, 9]
    assert [r["x"] for r in ds.sort("x", descending=True).take_all()] == \
        [9, 7, 5, 3, 1]


def test_limit():
    assert rd.range(10_000).limit(123).count() == 123


def test_aggregates():
    ds = rd.range(100)
    assert ds.min("id") == 0
    assert ds.max("id") == 99
    assert ds.mean("id") == pytest.approx(49.5)


def test_union_zip():
    a = rd.range(10)
    b = rd.range(10)
    assert a.union(b).count() == 20
    z = rd.range(5).zip(rd.range(5).map_batches(
        lambda blk: {"other": blk["id"] * 10}))
    rows = z.take_all()
    assert all(r["other"] == r["id"] * 10 for r in rows)


def test_parquet_csv_json_roundtrip(tmp_path):
    ds = rd.range(100).map_batches(
        lambda b: {"id": b["id"], "sq": b["id"] ** 2})
    for fmt in ("parquet", "csv", "json"):
        out = str(tmp_path / fmt)
        getattr(ds, f"write_{fmt}")(out)
        files = os.listdir(out)
        assert files
        back = getattr(rd, f"read_{fmt}")(out)
        assert back.count() == 100
        assert back.sum("sq") == sum(i * i for i in range(100))


def test_actor_pool_map_batches():
    class AddState:
        def __init__(self):
            self.offset = 1000

        def __call__(self, block):
            return {"id": block["id"] + self.offset}

    ds = rd.range(64).map_batches(AddState,
                                  compute=rd.ActorPoolStrategy(size=2))
    vals = sorted(r["id"] for r in ds.take_all())
    assert vals == list(range(1000, 1064))


def test_streaming_split_partitions_all_rows():
    ds = rd.range(300)
    its = ds.streaming_split(3)
    seen = []
    for it in its:
        for batch in it.iter_batches(batch_size=50, prefetch_batches=0):
            seen.extend(batch["id"].tolist())
    assert sorted(seen) == list(range(300))


def test_device_prefetch_to_jax():
    import jax

    ds = rd.range(64)
    batches = list(ds.iter_batches(batch_size=32,
                                   device=jax.devices("cpu")[0]))
    assert len(batches) == 2
    assert all(hasattr(b["id"], "devices") for b in batches)


def test_from_pandas_arrow_numpy():
    import pandas as pd
    import pyarrow as pa

    df = pd.DataFrame({"a": [1, 2, 3], "b": ["x", "y", "z"]})
    assert rd.from_pandas(df).count() == 3
    assert rd.from_arrow(pa.table({"a": [1, 2]})).count() == 2
    ds = rd.from_numpy(np.ones((4, 2)))
    assert ds.count() == 4


def test_schema_and_columns():
    ds = rd.range(5).map_batches(lambda b: {"id": b["id"],
                                            "f": b["id"].astype(np.float32)})
    schema = ds.schema()
    assert schema["id"] == "int64"
    assert schema["f"] == "float32"


def test_streaming_split_equal_block_counts():
    ds = rd.range(400, parallelism=8)  # 8 even blocks of 50 rows
    its = ds.streaming_split(2)
    import threading
    counts = [0, 0]

    def drain(i):
        for _ in its[i].iter_batches(batch_size=50, prefetch_batches=0):
            counts[i] += 1

    ts = [threading.Thread(target=drain, args=(i,)) for i in (0, 1)]
    [t.start() for t in ts]
    [t.join(timeout=30) for t in ts]
    assert counts[0] == counts[1] == 4


def test_early_break_does_not_leak_prefetch_thread():
    """The producers of five abandoned iterations end.  They are counted
    by name: the process's thread count also moves with the pools of
    whatever file this worker ran before."""
    import threading
    import time

    def producers():
        return [t for t in threading.enumerate()
                if t.name == "batch-prefetch"]

    before = len(producers())
    for _ in range(5):
        for batch in rd.range(10_000).iter_batches(batch_size=100):
            break
    deadline = time.monotonic() + 10
    while len(producers()) > before and time.monotonic() < deadline:
        time.sleep(0.01)
    assert len(producers()) <= before
