"""Roofline-driven autotuner battery: geometry buckets, tuning-table
persistence + schema validation, the resolve seam, config-invariance
(bit-exactness across the whole candidate lattice on every counting path),
derived chooser thresholds, staleness feedback, and telemetry exposure.
"""
import json
import os

import numpy as np
import pytest

from repro import obs
from repro.mining.dense import DenseDB, dense_mine_frequent
from repro.mining.gfp_backend import GFPBackend
from repro.mining.plan import choose_chunk_rows
from repro.mining.stream import StreamingDB, streaming_counts
from repro.roofline import autotune
from repro.roofline.autotune import (ACCUM_LATTICE, BLOCK_K_LATTICE,
                                     DEFAULT_ACCUM, DEFAULT_BLOCK_K,
                                     DEFAULT_BLOCK_N, LaunchConfig,
                                     TableEntry, TableError, TuningTable,
                                     load_table, resolve_launch_config,
                                     save_table, table_from_dict,
                                     table_to_dict)
from repro.roofline.kernel_model import (GEOMETRY_OVERFLOW,
                                         MAX_GEOMETRY_BUCKETS,
                                         _reset_geometry_buckets,
                                         _SEEN_BUCKETS, bucket_shape,
                                         geometry_bucket, record_launch)

from _pbt import given, settings, strategies as st


def _mk_table(entries, kind="cpu", source="<test>"):
    return TuningTable(device_kind=kind, entries=entries, source=source)


def _entry(block_k=128, accum="vpu_int32", chunk_rows=None, us=100.0,
           efficiency=0.5, candidates=None, chunk_candidates=None,
           serve_block_k=None):
    return TableEntry(
        config=LaunchConfig(block_k=block_k, block_n=DEFAULT_BLOCK_N,
                            accum=accum, chunk_rows=chunk_rows,
                            source="table"),
        us=us, efficiency=efficiency, candidates=candidates or {},
        chunk_candidates=chunk_candidates or {},
        serve_block_k=serve_block_k)


def _small_db(seed=0, rows=300, items=10):
    rng = np.random.default_rng(seed)
    tx = [list(np.flatnonzero(rng.random(items) < 0.4)) for _ in range(rows)]
    y = (rng.random(rows) < 0.3).astype(int)
    return tx, y


# -- geometry buckets --------------------------------------------------------

def test_bucket_rounds_up_and_clamps():
    assert geometry_bucket(1000, 100, 2, 3) == "n1024_k128_w2_c4"
    assert geometry_bucket(1, 1, 1, 1) == "n128_k8_w1_c1"          # floors
    assert geometry_bucket(1 << 30, 1 << 22, 100, 50) == \
        f"n{1 << 26}_k{1 << 20}_w64_c16"                           # ceilings
    # already a power of two: unchanged (round UP, not to nearest)
    assert geometry_bucket(2048, 256, 4, 2) == "n2048_k256_w4_c2"


def test_bucket_shape_roundtrip_and_rejection():
    assert bucket_shape("n2048_k256_w4_c2") == (2048, 256, 4, 2)
    with pytest.raises(ValueError):
        bucket_shape(GEOMETRY_OVERFLOW)
    with pytest.raises(ValueError):
        bucket_shape("n12_k8")


def test_record_launch_uses_buckets_and_overflow_cap():
    saved = set(_SEEN_BUCKETS)
    obs.reset()
    _reset_geometry_buckets()
    try:
        record_launch(1000, 100, 2, 3, 1e-3)
        record_launch(1001, 101, 2, 3, 1e-3)   # same bucket
        snap = obs.snapshot()
        launches = snap["counters"]["kernel_launches_total"]
        assert launches == {"geometry=n1024_k128_w2_c4": 2.0}
        # fill the cap; the next NEW bucket collapses to overflow
        for i in range(MAX_GEOMETRY_BUCKETS - 1):
            _SEEN_BUCKETS.add(f"synthetic{i}")
        record_launch(1 << 20, 8, 1, 1, 1e-3)
        eff = obs.kernel_efficiency()
        assert GEOMETRY_OVERFLOW in eff
        # known buckets still record under their own label past the cap
        record_launch(1000, 100, 2, 3, 1e-3)
        snap = obs.snapshot()
        assert snap["counters"]["kernel_launches_total"][
            "geometry=n1024_k128_w2_c4"] == 3.0
    finally:
        _reset_geometry_buckets()
        _SEEN_BUCKETS.update(saved)
        obs.reset()


# -- table persistence + schema ----------------------------------------------

def test_table_json_roundtrip(tmp_path):
    t = _mk_table({
        "n1024_k256_w2_c2": _entry(block_k=512, chunk_rows=4096, us=42.0,
                                   candidates={"bk512/vpu_int32": 42.0,
                                               "bk256/vpu_int32": 50.0},
                                   chunk_candidates={"0": 60.0,
                                                     "4096": 42.0},
                                   serve_block_k=64),
        "n4096_k256_w1_c1": _entry(block_k=64, accum="mxu_f32", us=13.0),
    }, kind="cpu")
    path = str(tmp_path / "cpu.json")
    save_table(t, path)
    back = load_table(path)
    assert back.device_kind == "cpu"
    assert back.source == path
    assert set(back.entries) == set(t.entries)
    e = back.entries["n1024_k256_w2_c2"]
    assert e.config == LaunchConfig(512, DEFAULT_BLOCK_N, "vpu_int32",
                                    4096, "table")
    assert e.us == 42.0
    assert e.candidates["bk256/vpu_int32"] == 50.0
    assert e.serve_block_k == 64
    assert back.entries["n4096_k256_w1_c1"].config.accum == "mxu_f32"
    assert back.entries["n4096_k256_w1_c1"].config.chunk_rows is None
    assert back.entries["n4096_k256_w1_c1"].serve_block_k is None


@pytest.mark.parametrize("mutate", [
    lambda d: d.update(schema=99),
    lambda d: d.update(device_kind=""),
    lambda d: d.update(entries="nope"),
    lambda d: d["entries"].update({"not_a_bucket": d["entries"].pop(
        "n1024_k256_w2_c2")}),
    lambda d: d["entries"]["n1024_k256_w2_c2"].update(block_k=100),
    lambda d: d["entries"]["n1024_k256_w2_c2"].update(accum="int8"),
    lambda d: d["entries"]["n1024_k256_w2_c2"].update(chunk_rows=-1),
    lambda d: d["entries"]["n1024_k256_w2_c2"].update(us=0),
    lambda d: d["entries"]["n1024_k256_w2_c2"].update(serve_block_k=100),
    lambda d: d["entries"]["n1024_k256_w2_c2"].update(block_n=1000),
])
def test_table_schema_rejection(mutate):
    doc = table_to_dict(_mk_table({"n1024_k256_w2_c2": _entry()}))
    mutate(doc)
    with pytest.raises(TableError):
        table_from_dict(doc)


def test_load_table_rejects_bad_json(tmp_path):
    p = tmp_path / "cpu.json"
    p.write_text("{not json")
    with pytest.raises(TableError):
        load_table(str(p))


def test_discovery_env_override_and_disable(tmp_path, monkeypatch):
    path = str(tmp_path / "mine.json")
    save_table(_mk_table({"n1024_k256_w2_c2": _entry(block_k=64)},
                         kind="whatever"), path)
    monkeypatch.setenv("REPRO_TUNE_TABLE", path)
    autotune.clear_active_table()
    try:
        t = autotune.active_table()
        assert t is not None and t.source == path
        assert resolve_launch_config(1000, 200, 2, 2).block_k == 64
        # REPRO_AUTOTUNE=0 wins over everything
        monkeypatch.setenv("REPRO_AUTOTUNE", "0")
        autotune.clear_active_table()
        assert autotune.active_table() is None
        assert resolve_launch_config(1000, 200, 2, 2).source == "default"
    finally:
        autotune.set_active_table(None)


def test_discovery_skips_corrupt_table(tmp_path, monkeypatch):
    path = tmp_path / "broken.json"
    path.write_text("{definitely not json")
    monkeypatch.setenv("REPRO_TUNE_TABLE", str(path))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    # keep discovery away from any real user cache / repo table
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "empty"))
    autotune.clear_active_table()
    try:
        before = obs.counter_total(obs.snapshot(), "autotune_table_errors_total")
        t = autotune.active_table()
        after = obs.counter_total(obs.snapshot(), "autotune_table_errors_total")
        assert after == before + 1
        # fell through to the repo table or None — never the corrupt file
        assert t is None or t.source != str(path)
    finally:
        autotune.set_active_table(None)


# -- the resolve seam --------------------------------------------------------

def test_resolve_defaults_without_table():
    autotune.set_active_table(None)
    cfg = resolve_launch_config(5000, 100, 2, 1)
    assert (cfg.block_k, cfg.block_n, cfg.accum, cfg.chunk_rows) == \
        (DEFAULT_BLOCK_K, DEFAULT_BLOCK_N, DEFAULT_ACCUM, None)
    assert cfg.source == "default"


def test_resolve_hits_matching_bucket_and_misses_fall_back():
    bucket = geometry_bucket(5000, 100, 2, 1)
    autotune.set_active_table(_mk_table({bucket: _entry(block_k=512)}))
    assert resolve_launch_config(5000, 100, 2, 1).block_k == 512
    # different bucket -> default
    assert resolve_launch_config(50, 100, 2, 1).block_k == DEFAULT_BLOCK_K


def test_resolve_mxu_guard_falls_back_to_vpu():
    # resolution hands out the tuned entry as it is; the exactness guard
    # runs at launch, on the launch's weights: a class weight sum of 2^24
    # over a handful of rows must not reach the f32 accumulator
    from repro.kernels.itemset_count import itemset_counts, ops

    bucket = geometry_bucket(8, 8, 1, 1)
    autotune.set_active_table(_mk_table({bucket: _entry(accum="mxu_f32",
                                                        block_k=64)}))
    cfg = resolve_launch_config(8, 8, 1, 1)
    assert (cfg.accum, cfg.block_k) == ("mxu_f32", 64)
    tx = np.full((8, 1), 1, np.uint32)
    masks = np.ones((8, 1), np.uint32)
    heavy = np.full((8, 1), 1 << 21, np.int32)           # sum == 2^24
    assert not ops.mxu_f32_exact(heavy)
    assert ops.checked_accum(None, cfg.accum, heavy) == "vpu_int32"
    assert ops.checked_accum(None, cfg.accum, heavy // 2) == "mxu_f32"
    got = np.asarray(itemset_counts(tx, masks, heavy))
    assert (got == 1 << 24).all()


def test_resolve_serve_block_k_uses_store_geometry():
    class Store:
        base_rows = 5000
        n_classes = 1

        class vocab:
            n_words = 2

    bucket = geometry_bucket(5000, DEFAULT_BLOCK_K, 2, 1)
    autotune.set_active_table(_mk_table(
        {bucket: _entry(block_k=512, serve_block_k=64)}))
    # only the padding-aware serve view steers the batcher — never the
    # fixed-K winner (different objective)
    assert autotune.resolve_serve_block_k(Store()) == 64
    autotune.set_active_table(_mk_table({bucket: _entry(block_k=512)}))
    assert autotune.resolve_serve_block_k(Store()) == DEFAULT_BLOCK_K
    autotune.set_active_table(None)
    assert autotune.resolve_serve_block_k(Store()) == DEFAULT_BLOCK_K
    assert autotune.resolve_serve_block_k(object()) == DEFAULT_BLOCK_K


def test_choose_chunk_rows_honors_table():
    bucket = geometry_bucket(100000, DEFAULT_BLOCK_K, 2, 2)
    autotune.set_active_table(_mk_table(
        {bucket: _entry(chunk_rows=5000)}))
    # tuned value, aligned down to the kernel N-block
    assert choose_chunk_rows(2, 2, n_rows=100000) == 4096
    # no n_rows -> pure heuristic, table untouched
    heur = choose_chunk_rows(2, 2)
    autotune.set_active_table(None)
    # table gone: the heuristic again, clamped to the aligned row count (the
    # 64MB staging budget allows far more rows than the DB has)
    assert choose_chunk_rows(2, 2, n_rows=100000) == min(heur, 100352)


def test_choose_chunk_rows_clamped_to_db_rows():
    """A tuned chunk_rows measured on a bigger bucket must be clamped to the
    aligned row count: handing a 2k-row DB a 16384-row chunk would zero-pad
    the single ragged chunk 8x (regression for the padding-waste bug)."""
    bucket = geometry_bucket(2000, DEFAULT_BLOCK_K, 2, 2)
    autotune.set_active_table(_mk_table({bucket: _entry(chunk_rows=16384)}))
    try:
        got = choose_chunk_rows(2, 2, n_rows=2000)
    finally:
        autotune.set_active_table(None)
    assert got == 2048                       # align_up(2000, 1024), not 16384
    # the budget heuristic clamps the same way (64MB budget >> 2000 rows)
    assert choose_chunk_rows(2, 2, n_rows=2000) == 2048
    # custom align: clamp rounds the row count up to one aligned chunk
    assert choose_chunk_rows(4, 2, budget_bytes=1 << 30, align=128,
                             n_rows=300) == 384
    # clamping never produces a chunk below one align unit
    assert choose_chunk_rows(2, 2, n_rows=1) == 1024


def test_oversized_tuned_chunk_never_launches_past_padded_rows(monkeypatch):
    """With a tuned table demanding oversized chunks, no streamed launch may
    exceed the align-rounded DB row count (the lattice-invariance battery's
    launch-size bound)."""
    import repro.mining.stream as stream_mod

    tx, y = _small_db(3, rows=300, items=10)
    db = DenseDB.encode(tx, classes=y, n_classes=2)
    bits, wts = np.asarray(db.bits), np.asarray(db.weights)
    n_unique = bits.shape[0]
    bucket = geometry_bucket(n_unique, DEFAULT_BLOCK_K, bits.shape[1], 2)
    masks = bits[:8].copy()
    from repro.kernels.itemset_count import itemset_counts
    want = np.asarray(itemset_counts(db.bits, masks, db.weights))

    launched = []
    real = stream_mod.itemset_counts_into

    def spy(acc, cur_tx, tgt, w, **kw):
        launched.append(int(cur_tx.shape[0]))
        return real(acc, cur_tx, tgt, w, **kw)

    monkeypatch.setattr(stream_mod, "itemset_counts_into", spy)
    autotune.set_active_table(_mk_table({bucket: _entry(chunk_rows=16384)}))
    try:
        sdb = StreamingDB.from_arrays(db.vocab, bits, wts, db.n_rows, 2)
        got = np.asarray(sdb.counts(masks))
    finally:
        autotune.set_active_table(None)
    assert launched, "streamed sweep never launched"
    bound = -(-n_unique // 1024) * 1024
    assert max(launched) <= bound, (launched, bound)
    np.testing.assert_array_equal(got, want)


# -- config invariance: the whole lattice is bit-exact -----------------------

_LATTICE = [(bk, acc) for bk in BLOCK_K_LATTICE for acc in ACCUM_LATTICE]


@settings(max_examples=12, deadline=None)
@given(st.sampled_from(_LATTICE), st.integers(0, 10 ** 6))
def test_lattice_config_invariance_all_paths(cfg, seed):
    """Any lattice config produces bit-identical counts to the default on
    the dense, streaming, and GFP paths (speed may change, counts never)."""
    block_k, accum = cfg
    tx, y = _small_db(seed)
    db = DenseDB.encode(tx, classes=y, n_classes=2)
    bits = np.asarray(db.bits)
    wts = np.asarray(db.weights)
    masks = bits[:12].copy()

    from repro.kernels.itemset_count import itemset_counts
    want = np.asarray(itemset_counts(db.bits, masks, db.weights))
    got_dense = np.asarray(itemset_counts(db.bits, masks, db.weights,
                                          block_k=block_k, accum=accum))
    np.testing.assert_array_equal(got_dense, want)

    got_stream = np.asarray(streaming_counts(
        bits, masks, wts, chunk_rows=64, block_k=block_k, accum=accum))
    np.testing.assert_array_equal(got_stream, want)

    # GFP hybrid: force kernel blocks (host_rows=0) under the lattice config
    # via an active table covering every bucket this problem can hit
    table = _mk_table({
        geometry_bucket(n, k, bits.shape[1], wts.shape[1]): _entry(
            block_k=block_k, accum=accum)
        for n in (128, 256, 512, 1024)
        for k in (8, 16, 32, 64, 128, 256)})
    autotune.set_active_table(table)
    try:
        be = GFPBackend(db, host_rows=0)
        got_gfp_tuned = be.counts(masks)
    finally:
        autotune.set_active_table(None)
    be = GFPBackend(db, host_rows=0)
    got_gfp_default = be.counts(masks)
    np.testing.assert_array_equal(got_gfp_tuned, got_gfp_default)


def test_tuned_table_mine_identical_to_default():
    """End-to-end: a full mine under an aggressive tuning table returns the
    exact result dict of the default-config mine."""
    tx, y = _small_db(7, rows=400, items=12)
    db = DenseDB.encode(tx, classes=y, n_classes=2)
    want = dense_mine_frequent(db, 40)
    table = _mk_table({
        geometry_bucket(n, k, 1, 2): _entry(block_k=64, accum="mxu_f32",
                                            chunk_rows=1024)
        for n in (128, 256, 512, 1024)
        for k in (8, 16, 32, 64, 128, 256, 512, 1024)})
    autotune.set_active_table(table)
    try:
        got = dense_mine_frequent(db, 40)
    finally:
        autotune.set_active_table(None)
    assert got == want


# -- derived chooser thresholds ----------------------------------------------

def _throughput_table(overhead_us=100.0, per_row_us=0.05, rho=1.0):
    """Synthetic table whose winner timings follow us = overhead + per_row*n
    and whose chunk candidates encode a single-pass/chunked ratio rho."""
    entries = {}
    for n in (1024, 4096, 16384, 65536):
        us = overhead_us + per_row_us * n
        entries[geometry_bucket(n, 256, 2, 2)] = _entry(
            us=us, chunk_rows=None,
            chunk_candidates={"0": us, "4096": us / rho})
    return _mk_table(entries)


def test_derived_thresholds_scale_with_measured_overhead():
    from repro.mining.stream import device_stream_threshold_bytes

    residency = device_stream_threshold_bytes()
    base = autotune.derived_chooser_thresholds(_throughput_table())
    assert base["tiny_rows"] == 2000          # overhead / per_row
    assert base["min_depth"] == 4             # overhead == reference
    assert base["gfp_host_rows"] == 4096      # floored at the hybrid default
    assert base["stream_threshold_bytes"] == residency // 2

    pricey = autotune.derived_chooser_thresholds(
        _throughput_table(overhead_us=400.0))
    assert pricey["tiny_rows"] == 8000
    assert pricey["min_depth"] == 2           # 4 - log2(4)
    cheap = autotune.derived_chooser_thresholds(
        _throughput_table(overhead_us=25.0))
    assert cheap["min_depth"] == 6            # 4 - log2(1/4)

    # expensive chunking (chunked 2x slower than single pass) raises the
    # residency threshold; free chunking (rho ~ 2) lowers it
    slow_chunk = autotune.derived_chooser_thresholds(
        _throughput_table(rho=0.25))
    assert slow_chunk["stream_threshold_bytes"] == 2 * residency

    assert autotune.derived_chooser_thresholds(_mk_table({})) == {}
    autotune.set_active_table(None)
    assert autotune.derived_chooser_thresholds() == {}


def test_chooser_consumes_derived_thresholds():
    from repro.mining.chooser import DatasetTraits, choose_backend

    traits = DatasetTraits(n_rows=5000, n_unique=5000, vocab_size=20,
                           n_classes=1, nbytes=10 ** 6, density=0.05,
                           skew=1.0, dedup_ratio=1.0)
    autotune.set_active_table(None)
    assert choose_backend(traits).name == "dense"   # 5000 >= default 2048
    # a table measuring very expensive launches pushes tiny_rows above 5000:
    # the same traits now pick dense VIA the tiny-DB rule (reason changes)
    autotune.set_active_table(_throughput_table(overhead_us=400.0))
    try:
        choice = choose_backend(traits)
        assert choice.name == "dense"
        assert "tiny DB" in choice.reason          # 5000 < derived 8000
    finally:
        autotune.set_active_table(None)


# -- sweep + staleness -------------------------------------------------------

def test_sweep_smoke_produces_valid_winning_table(tmp_path):
    t = autotune.sweep([(256, 16, 1, 1)], repeats=1,
                       block_ks=(128, 256), accums=("vpu_int32",),
                       chunk_grid=(0,), kind="testkind")
    assert set(t.entries) == {geometry_bucket(256, 16, 1, 1)}
    e = t.entries[geometry_bucket(256, 16, 1, 1)]
    assert e.config.block_k in (128, 256)
    # the CPU has no entry in the peaks table: no efficiency ratio
    assert e.us > 0 and e.efficiency == 0.0
    assert set(e.candidates) == {"bk128/vpu_int32", "bk256/vpu_int32"}
    # k=16 can't shrink under any candidate block — no serve view
    assert e.serve_block_k is None and e.serve_candidates == {}
    # round-trips through the schema checker
    path = save_table(t, str(tmp_path / "testkind.json"))
    assert load_table(path).entries.keys() == t.entries.keys()


def test_sweep_serve_view_prefers_less_padding(monkeypatch):
    """The serve view launches each candidate at k = block_k (the batcher
    pads a flush up to the block), so for the reference batch the small
    block's padded flush does 4x less containment work than the default
    block's — the structural effect the fixed-K candidates cannot see.
    Asserted on the launched geometry, not on a CPU timing."""
    import repro.kernels.itemset_count as kic

    launched = []
    real = kic.itemset_counts

    def recording(tx, tgt, wts, **kw):
        launched.append((int(tx.shape[0]), int(tgt.shape[0]),
                         kw["block_k"]))
        return real(tx, tgt, wts, **kw)

    monkeypatch.setattr(kic, "itemset_counts", recording)
    n = 2048
    t = autotune.sweep([(n, 256, 2, 2)], repeats=1, block_ks=(64, 256),
                       accums=("vpu_int32",), chunk_grid=(0,),
                       kind="testkind")
    e = t.entries[geometry_bucket(n, 256, 2, 2)]
    assert set(e.serve_candidates) == {"64", "256"}
    assert all(bk in BLOCK_K_LATTICE for bk in (64, 256))
    # contained pairs per launch = rows x padded targets
    work = {}
    for rows, k, bk in launched:
        if k == bk:                      # the serve view: k = block_k
            work[bk] = rows * k
    flushes = {bk: -(-autotune.SERVE_REF_BATCH // bk) for bk in (64, 256)}
    padded = {bk: flushes[bk] * work[bk] for bk in (64, 256)}
    assert padded[64] * 4 == padded[256]
    assert padded[64] == n * autotune.SERVE_REF_BATCH


def test_sweep_leaves_telemetry_clean():
    obs.reset()
    autotune.sweep([(256, 16, 1, 1)], repeats=1, block_ks=(256,),
                   accums=("vpu_int32",), chunk_grid=(0,))
    assert obs.counter_total(obs.snapshot(), "kernel_launches_total") == 0
    assert obs.KERNEL_TIMING        # restored
    obs.reset()


def test_staleness_flags_drifted_entry():
    bucket = geometry_bucket(4096, 256, 2, 2)
    entry = _entry(block_k=512, us=100.0, efficiency=0.5,
                   candidates={"bk512/vpu_int32": 100.0,
                               "bk256/vpu_int32": 120.0})
    table = _mk_table({bucket: entry})
    # live ledger says this bucket now runs at efficiency 0.2 — well below
    # the runner-up's sweep-time 0.5 * (100/120) ~ 0.417 (x0.9 margin)
    obs.reset()
    obs.REGISTRY.counter("kernel_launches_total", geometry=bucket).inc(10)
    obs.REGISTRY.counter("kernel_measured_s_total", geometry=bucket).inc(1.0)
    obs.REGISTRY.counter("kernel_predicted_s_total", geometry=bucket).inc(0.2)
    rep = autotune.staleness_report(table)
    assert rep[bucket]["stale"] is True
    assert rep[bucket]["alternative"] == "bk256/vpu_int32"
    # healthy live efficiency: not stale
    obs.reset()
    obs.REGISTRY.counter("kernel_launches_total", geometry=bucket).inc(10)
    obs.REGISTRY.counter("kernel_measured_s_total", geometry=bucket).inc(1.0)
    obs.REGISTRY.counter("kernel_predicted_s_total", geometry=bucket).inc(0.5)
    rep = autotune.staleness_report(table)
    assert rep[bucket]["stale"] is False
    # no launches recorded: not stale, reason says why
    obs.reset()
    rep = autotune.staleness_report(table)
    assert rep[bucket]["stale"] is False and "reason" in rep[bucket]
    obs.reset()


def test_server_stats_expose_autotune_section():
    from repro.serve import CountServer

    tx, y = _small_db(3, rows=120, items=8)
    bucket = geometry_bucket(5000, 256, 1, 2)
    with CountServer(tx, classes=y, n_classes=2) as server:
        autotune.set_active_table(_mk_table({bucket: _entry(block_k=512)},
                                            source="<pinned>"))
        try:
            sec = server.stats()["telemetry"]["autotune"]
        finally:
            autotune.set_active_table(None)
        assert sec["active"] is True
        assert sec["source"] == "<pinned>"
        assert sec["entries"][bucket]["block_k"] == 512
        assert bucket in sec["stale"]
        sec_off = server.stats()["telemetry"]["autotune"]
        assert sec_off == {"active": False, "source": "default",
                           "entries": {}, "stale": {},
                           "fallbacks": dict(autotune.LAST_FALLBACKS)}


def test_describe_active_banner():
    autotune.set_active_table(None)
    assert "default launch configs" in autotune.describe_active()
    autotune.set_active_table(_mk_table({"n128_k8_w1_c1": _entry()},
                                        kind="cpu", source="x.json"))
    try:
        msg = autotune.describe_active()
        assert "cpu" in msg and "1 entries" in msg and "x.json" in msg
    finally:
        autotune.set_active_table(None)
