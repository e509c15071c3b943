"""The benchmark's record of the words each count's targets hold, at the
call into the store: by hand on known keys, through the synchronous query
path and the async flusher, and nothing while the tracer is off or the
record is not asked for."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from bench import target_words  # noqa: E402
from bench.readings import priced_launches  # noqa: E402

# items 0 .. 99 in 500 rows: a vocabulary of 4 words
ROWS = [[(7 * r + j * 13) % 100 for j in range(4)] for r in range(500)]
UNKNOWN = 10_000


@pytest.fixture
def tracer():
    from repro import obs

    obs.reset()
    obs.configure(tracing=True)
    yield obs.TRACER
    obs.reset()


def _server(**kw):
    from repro.serve import CountServer

    return CountServer(ROWS, classes=[r % 2 for r in range(500)],
                       n_classes=2, **kw)


def _by_hand(server, keys):
    """(sum over known keys of the words each holds, words some key
    holds)."""
    vocab = server.store.vocab
    held, union = 0, set()
    for key in keys:
        if all(a in vocab for a in key):
            words = {vocab.col(a) // 32 for a in key}
            held += len(words)
            union |= words
    return held, len(union)


def _keys(server):
    """Keys whose items lie in one word (one item, and two), in two, in
    three, and a key with an item that no row holds."""
    items = server.store.vocab.items
    return [(items[0],), (items[1], items[2]), (items[3], items[40]),
            (items[5], items[40], items[70]), (items[33], UNKNOWN)]


def _instants(tracer):
    return [s for s in tracer.spans() if s.name == target_words.INSTANT]


def test_query_records_the_words_of_its_targets(tracer):
    server = _server()
    keys = _keys(server)
    held, union = _by_hand(server, keys)
    assert (held, union) == (1 + 1 + 2 + 3, 3)
    with target_words.recorded({"server": server}, True):
        server.query(keys)
    (inst,) = _instants(tracer)
    assert inst.attrs == {"mask_words": held, "union_words": union}
    # beneath the span that records the count's targets, beside its
    # launches, which the reading prices by them
    parent = next(s for s in tracer.spans() if s.span_id == inst.parent_id)
    assert parent.name == "serve.count"
    assert parent.attrs["n_masks"] == len(keys)
    priced = priced_launches({"spans": tracer.spans()})
    assert priced and all(p[4:] == (held, union) for p in priced)
    assert "counts_masks" not in vars(server.store)
    server.close()


def test_the_async_flusher_records_beneath_its_count(tracer):
    server = _server(async_flush=True)
    keys = _keys(server)
    with target_words.recorded({"server": server}, True):
        fut = server.submit_async("client-0", keys)
        fut.result(60.0)
    server.close()
    (inst,) = _instants(tracer)
    assert (inst.attrs["mask_words"], inst.attrs["union_words"]) == \
        _by_hand(server, keys)
    parent = next(s for s in tracer.spans() if s.span_id == inst.parent_id)
    assert parent.name == "serve.count"


def test_nothing_is_recorded_with_the_tracer_off_or_unasked(tracer):
    from repro import obs

    server = _server()
    keys = _keys(server)
    with target_words.recorded({"server": server}, False):
        assert "counts_masks" not in vars(server.store)
        server.query(keys)
    assert _instants(tracer) == []
    tracer.reset()
    obs.configure(tracing=False)
    with target_words.recorded({"server": server}, True):
        server.query([keys[1]])
    assert _instants(tracer) == [] and tracer.spans() == []
    server.close()


def test_a_driver_without_a_store_is_left_alone():
    with target_words.recorded({}, True):
        pass
    with target_words.recorded({"server": object()}, True):
        pass


def test_words_of_a_block_with_pad_rows():
    masks = np.zeros((8, 4), np.uint32)
    masks[0, 1] = 0b1
    masks[1, [1, 3]] = 0b101
    assert target_words.words_of(masks) == {"mask_words": 3,
                                            "union_words": 2}


@pytest.mark.parametrize("cell", ["sim4m-serve", "sim4m-bulk"])
def test_a_traced_run_prices_no_launch_dense(cell, monkeypatch):
    """The harness records the words of every count in a traced window:
    each launch the roofline reading prices has them."""
    import time

    from bench import harness, readings
    from bench._tiny import SECONDS, tiny_sizes

    seen = []

    def spy(ctx):
        got = priced_launches(ctx)
        seen.extend(got)
        return got

    monkeypatch.setattr(readings, "priced_launches", spy)
    r = harness.run_cell(cell, 2**31 + 91, SECONDS, True,
                         t0=time.perf_counter(), require_tpu=False,
                         overrides=tiny_sizes(cell))
    assert r["correct"] is True
    assert seen and all(p[4] is not None and p[5] is not None
                        for p in seen)
    assert all(0 < p[5] <= p[2] and 0 < p[4] <= p[1] * p[2] for p in seen)
