"""``inside.py`` end to end on the CPU at the tiny size: one traced
window with due-time arrivals gives the program's spans, the latency
split and the work counters. A CPU trace has no device plane, so no
device split. Nothing here is a measurement."""
import time

import pytest

import inside
import run
import tiny


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    import jax
    before = jax.config.jax_default_matmul_precision
    b = run.Bench(run.Cell.load(
        tiny.make_root(tmp_path_factory.mktemp("root")), "tiny-live"))
    yield b
    b.compiles.close()
    b.gcs.close()
    jax.config.update("jax_default_matmul_precision", before)


def test_inside_one_window(bench):
    capture = inside.ChunkArgs(bench.pool.fleet)
    bench.prepare(2 ** 32 + 5)
    assert capture.args is not None          # the warm-up's first chunk
    bench._source = inside.due_arrivals(bench)
    try:
        res = inside.inside(bench, capture, 1.5, time.perf_counter())
    finally:
        del bench._source
    assert res["correct"]
    assert res["chunk_executions"] == 0 and res["device_ms_by_primitive"] \
        == {}
    assert capture.hlo().count("op_name=") > 0
    assert res["program_spans"] >= 4 * res["dispatches"] > 0
    spans = res["span_ms_per_chunk"]
    assert spans["dispatch"] >= spans["build"] + spans["h2d"] \
        + spans["enqueue"] > 0
    assert res["queue_wait_p50_ms"] >= 0 and res["in_flight_p50_ms"] > 0
    c = res["counters"]
    assert c["frames"] > 0 and c["tracks"] > 0 and c["flushes"] == 0
    assert set(c) == {"frames", "tracks", "consumed", "updates_applied",
                      "updates_shipped", "flushes"}
