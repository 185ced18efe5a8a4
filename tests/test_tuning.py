"""Tile-autotuner cache lifecycle and the XLA flag bundles.

The tuner is trace-time Python: the dispatch layer asks it for a tile
choice while building a jaxpr, and the answer must be stable across
processes (persisted JSON), survive a corrupted cache file, and be fully
inert under ``-kernel_tune off``.
"""

import json
import os
import warnings

import pytest

from repro.kernels import tuning
from repro.utils import xla_flags


@pytest.fixture(autouse=True)
def _isolated_tuner(tmp_path):
    """Every test runs against its own cache file and leaves the
    process-wide tuner state as it found it."""
    prev_enabled, prev_path = tuning.enabled(), tuning.cache_path()
    tuning.reset(cache_path=str(tmp_path / "autotune.json"))
    yield
    tuning.reset(cache_path=prev_path)
    tuning.configure(enabled=prev_enabled)


# A shape comfortably above MIN_TUNE_ELEMS so tune() actually measures.
BIG = dict(n=1 << 20, m=4, k=4)


def _tune(bench, *, candidates=(8, 16, 32), default=16, **over):
    kw = dict(BIG, **over)
    return tuning.tune("ell_backup_blocked", "cpu", kw["n"], kw["m"],
                       kw["k"], "float32", candidates, default, bench)


def test_round_trip_persists_and_reloads():
    calls = []

    def bench(cand):
        calls.append(cand)
        return {8: 3.0, 16: 1.0, 32: 2.0}[cand]

    assert _tune(bench) == 16
    assert calls, "bench was never invoked"
    # same key again: served from memory, no re-measurement
    calls.clear()
    assert _tune(bench) == 16
    assert not calls
    # a fresh process (reset) with the same cache file: served from disk
    path = tuning.cache_path()
    assert os.path.exists(path)
    tuning.reset(cache_path=path)
    assert _tune(bench) == 16
    assert not calls
    blob = json.load(open(path))
    [entry] = blob["entries"].values()
    assert entry["choice"] == 16
    assert set(entry["timings_s"]) == {"8", "16", "32"}


def test_n_bucket_shares_entries_across_close_sizes():
    assert tuning.n_bucket(1) == 1
    assert tuning.n_bucket(1000) == 1024
    assert tuning.n_bucket(1024) == 1024
    assert tuning.n_bucket(1025) == 2048
    k1 = tuning.cache_key("k", "cpu", 900_000, 4, 4, "float32")
    k2 = tuning.cache_key("k", "cpu", 1_000_000, 4, 4, "float32")
    assert k1 == k2
    assert k1 != tuning.cache_key("k", "cpu", 2_000_000, 4, 4, "float32")


def test_corrupt_cache_file_recovers(tmp_path):
    path = tuning.cache_path()
    with open(path, "w") as f:
        f.write("{not json")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert tuning.lookup("whatever") is None
        assert tuning.lookup("whatever") is None  # warns only once
    assert sum("unreadable" in str(x.message) for x in w) == 1
    # the next successful tune overwrites the corrupt file
    assert _tune(lambda c: float(c)) == 8
    assert json.load(open(path))["entries"]


def test_disabled_returns_default_and_writes_nothing():
    tuning.configure(enabled=False)
    calls = []
    assert _tune(lambda c: calls.append(c) or 1.0, default=42) == 42
    assert not calls
    assert not os.path.exists(tuning.cache_path())


def test_small_problem_skips_measurement():
    calls = []
    got = _tune(lambda c: calls.append(c) or 1.0, n=128, m=4, k=4,
                default=99)
    assert got == 99 and not calls


def test_tune_inside_trace_falls_back_to_default():
    """When the dispatch layer is traced inside an enclosing jit, the tuner
    must not try to time candidates (they would be staged into the trace) —
    it returns the default and records nothing, so a later eager call can
    still tune the shape."""
    import jax

    calls = []

    def traced(x):
        got = _tune(lambda c: calls.append(c) or 1.0, default=16)
        return x * got

    assert float(jax.jit(traced)(2.0)) == 32.0
    assert not calls
    assert not os.path.exists(tuning.cache_path())
    # eager call afterwards tunes for real
    assert _tune(lambda c: {8: 3.0, 16: 2.0, 32: 1.0}[c]) == 32
    assert os.path.exists(tuning.cache_path())


def test_failing_candidate_is_skipped():
    def bench(cand):
        if cand == 8:
            raise RuntimeError("boom")
        return {16: 2.0, 32: 1.0}[cand]

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        assert _tune(bench) == 32


def test_session_options_drive_tuner(tmp_path):
    from repro.api import Session

    path = str(tmp_path / "elsewhere.json")
    with Session({"-kernel_tune": "off", "-kernel_tune_cache": path}):
        assert tuning.enabled() is False
        assert tuning.cache_path() == path
    with Session({"-kernel_tune": "on"}):
        assert tuning.enabled() is True


# --------------------------------------------------------------------------- #
# XLA flag bundles                                                            #
# --------------------------------------------------------------------------- #

def test_bundles_render_and_merge_idempotently():
    for name in xla_flags.bundle_names():
        rendered = xla_flags.render(name)
        assert all(tok.startswith("--") and "=" in tok
                   for tok in rendered.split())
    merged = xla_flags.merged_flags("cpu-single", "--foo=bar")
    assert merged.startswith("--foo=bar")
    for flag, value in xla_flags.bundle("cpu-single").items():
        assert f"--{flag}={value}" in merged
    # re-merging replaces the bundle's own tokens instead of duplicating them
    again = xla_flags.merged_flags("cpu-single", merged)
    assert again.split().count("--foo=bar") == 1
    assert len(again.split()) == len(merged.split())


def test_unknown_bundle_raises_with_available_names():
    with pytest.raises(KeyError, match="cpu-single"):
        xla_flags.bundle("no-such-bundle")


def test_apply_bundle_sets_env():
    env = {"XLA_FLAGS": "--keep=me"}
    xla_flags.apply_bundle("cpu-host", env=env)
    assert "--keep=me" in env["XLA_FLAGS"]
    for flag, value in xla_flags.bundle("cpu-host").items():
        assert f"--{flag}={value}" in env["XLA_FLAGS"]


@pytest.mark.parametrize("name", xla_flags.bundle_names())
def test_bundle_goes_to_the_variable_its_compiler_reads(name):
    """TPU flags are libtpu's (jaxlib aborts on them in XLA_FLAGS); CPU
    flags are XLA's.  Each bundle leaves the other variable alone."""
    var = "LIBTPU_INIT_ARGS" if name.startswith("tpu-") else "XLA_FLAGS"
    other = ({"XLA_FLAGS", "LIBTPU_INIT_ARGS"} - {var}).pop()
    env = {var: "--keep=me", other: "--untouched=1"}
    xla_flags.apply_bundle(name, env=env)
    assert xla_flags.env_var(name) == var
    assert env[other] == "--untouched=1"
    assert env[var].startswith("--keep=me ")
    for flag, value in xla_flags.bundle(name).items():
        assert f"--{flag}={value}" in env[var]


def test_session_applies_bundle_option():
    from repro.api import Session

    # with the backend already initialized, Session must warn (flags cannot
    # take effect in this process) yet still set the env var
    import jax

    jax.devices()
    prev = os.environ.get("XLA_FLAGS")
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with Session({"-xla_flag_bundle": "cpu-host"}):
                pass
        assert any("backend" in str(x.message).lower() for x in w)
        for flag, value in xla_flags.bundle("cpu-host").items():
            assert f"--{flag}={value}" in os.environ.get("XLA_FLAGS", "")
    finally:
        if prev is None:
            os.environ.pop("XLA_FLAGS", None)
        else:
            os.environ["XLA_FLAGS"] = prev
