"""Runtime sanitizer tests (fishnet_tpu/utils/sanitize.py).

The seeded-violation tests are the teeth: a double delivery pushed
through the REAL LaneScheduler._deliver exactly-once point, and a real
post-donation read through a jit that donates — each must trip the
sanitizer with a message naming the site. The off-mode tests pin the
structural zero-overhead contract: guard_donation returns the wrapped
callable unchanged (the same object), so the default path cannot have
gained a frame.
"""
import types

import numpy as np
import pytest

from fishnet_tpu.utils import sanitize
from fishnet_tpu.utils.sanitize import SanitizeError


# ------------------------------------------------------ off-mode contract


def test_guard_donation_off_returns_fn_unchanged():
    def fn(x):
        return x

    assert sanitize.guard_donation("t::fn", fn, argnums=(0,)) is fn
    assert sanitize.guard_donation("t::fn", fn, force=False) is fn


def test_sanitize_defaults_off():
    # the suite runs without FISHNET_TPU_SANITIZE set; every
    # construction-time capture in the production modules sees False
    assert sanitize.enabled() is False


def test_sanitize_setting_reaches_engine_children():
    # engine=True in the registry: the supervised host child inherits
    # the flag through engine_env, so arming the parent arms the tree
    from fishnet_tpu.utils import settings

    entry = {s.name: s for s in settings.SETTINGS}["FISHNET_TPU_SANITIZE"]
    assert entry.engine and entry.kind == "bool" and entry.default == "0"


# ------------------------------------------------- donation poisoning


def test_seeded_post_donation_read_trips_sanitizer():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    jitted = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    guard = sanitize.guard_donation(
        "test::donating_jit", jitted, argnums=(0,), force=True)
    assert guard is not jitted  # forced on: wrapped

    x = jnp.arange(4, dtype=jnp.int32)
    y = guard(x)
    assert np.asarray(y).tolist() == [1, 2, 3, 4]
    # the input buffer is dead whether or not XLA:CPU actually donated
    # — the guard poisons what the platform left alive
    assert x.is_deleted()
    assert sanitize.deleted_site(x) == "test::donating_jit"
    # a direct read raises from JAX itself
    with pytest.raises(RuntimeError):
        np.asarray(x)
    # passing the dead handle back into a guarded call raises the
    # attributed error BEFORE JAX's siteless one
    with pytest.raises(SanitizeError, match="test::donating_jit"):
        guard(x)


def test_donation_guard_forwards_attributes():
    jax = pytest.importorskip("jax")

    jitted = jax.jit(lambda x: x + 1, donate_argnums=(0,))
    guard = sanitize.guard_donation(
        "test::attrs", jitted, argnums=(0,), force=True)
    # AOT tooling reaches .lower through the guard
    assert guard.lower == jitted.lower


def test_donation_guard_keyword_argnames():
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    jitted = jax.jit(lambda a, b: a + b, donate_argnames=("b",))
    guard = sanitize.guard_donation(
        "test::kw", jitted, argnames=("b",), force=True)
    a = jnp.arange(3, dtype=jnp.int32)
    b = jnp.arange(3, dtype=jnp.int32)
    guard(a, b=b)
    assert b.is_deleted() and not a.is_deleted()


# -------------------------------------------------- exactly-once ledgers


def _fake_scheduler(sanitize_on=True):
    """A LaneScheduler shell around the real _deliver: the exactly-once
    point itself, with the engine hooks stubbed out."""
    from fishnet_tpu.engine.tpu import LaneScheduler

    sched = LaneScheduler.__new__(LaneScheduler)
    sched._sanitize = sanitize_on
    sched.engine = types.SimpleNamespace(
        on_response=None, on_deliver=None, _warn=lambda msg: None)
    return sched


def test_seeded_double_delivery_trips_sanitizer():
    sched = _fake_scheduler(sanitize_on=True)
    entry = types.SimpleNamespace(responses={}, chunk=None)
    wp = types.SimpleNamespace(position_index=3, ctx=None)
    resp = object()

    sched._deliver(entry, wp, resp)
    assert entry.responses[3] is resp
    with pytest.raises(SanitizeError, match="LaneScheduler._deliver"):
        sched._deliver(entry, wp, resp)


def test_double_delivery_tolerated_when_off():
    # off-mode keeps the pre-sanitizer behavior bit-for-bit: last
    # write wins silently (the scheduler's own invariants prevent it;
    # the sanitizer is the net that PROVES they do)
    sched = _fake_scheduler(sanitize_on=False)
    entry = types.SimpleNamespace(responses={}, chunk=None)
    wp = types.SimpleNamespace(position_index=3, ctx=None)
    sched._deliver(entry, wp, "a")
    sched._deliver(entry, wp, "b")
    assert entry.responses[3] == "b"


def test_check_delivery_once():
    ledger = {}
    sanitize.check_delivery_once(ledger, "k", "t::site")
    ledger["k"] = 1
    with pytest.raises(SanitizeError, match="t::site"):
        sanitize.check_delivery_once(ledger, "k", "t::site")


def test_check_replay_consistent():
    ledger = {"fp": {"score": 10, "move": "e2e4"}}
    # identical replay is DESIGNED (journal resend after respawn)
    sanitize.check_replay_consistent(
        ledger, "fp", {"score": 10, "move": "e2e4"}, "t::journal")
    # unknown fingerprint: nothing to conflict with
    sanitize.check_replay_consistent(ledger, "other", {"x": 1}, "t::j")
    # same fingerprint, different payload: two answers for one position
    with pytest.raises(SanitizeError, match="conflicting"):
        sanitize.check_replay_consistent(
            ledger, "fp", {"score": -3, "move": "d2d4"}, "t::journal")


def test_supervisor_journal_replay_check_is_wired():
    # the duplicate-partial branch consults the sanitizer when armed;
    # source-level check so a refactor that drops the hook fails here
    import inspect

    from fishnet_tpu.engine import supervisor

    src = inspect.getsource(supervisor.SupervisedEngine._journal_record)
    assert "check_replay_consistent" in src


# ------------------------------------------------ in-flight stage labels


def test_inflight_strict_rejects_unknown_stage():
    from fishnet_tpu.obs.inflight import InflightRegistry

    reg = InflightRegistry()
    reg._strict = True
    reg.begin("t1", "r1", "tenant", "analyse")
    with pytest.raises(SanitizeError, match="unknown stage label"):
        reg.stage("t1", "despatched")  # typo'd label
    with pytest.raises(SanitizeError, match="unknown stage label"):
        reg.position("t1", 0, "lanes")
    # known labels keep working
    reg.stage("t1", "lane")
    reg.position("t1", 0, "delivered", lane=2)


def test_inflight_strict_clamps_backward_moves_without_raising():
    # re-dispatch after member loss legitimately replays positions
    # through earlier stages: clamped, NEVER an error
    from fishnet_tpu.obs.inflight import InflightRegistry

    reg = InflightRegistry()
    reg._strict = True
    reg.begin("t1", "r1", "tenant", "analyse")
    reg.stage("t1", "lane")
    reg.stage("t1", "admitted")  # backward: ignored
    snap = reg.snapshot()
    assert snap[0]["stage"] == "lane"


def test_inflight_lax_mode_ignores_unknown_stage():
    from fishnet_tpu.obs.inflight import InflightRegistry

    reg = InflightRegistry()
    assert reg._strict is False  # default: flag unset
    reg.begin("t1", "r1", "tenant", "analyse")
    reg.stage("t1", "despatched")  # silently ranked 0, as before


# ---------------------------------------------------------- TT integrity


def _meta(score, depth, flag):
    # mirror ops/tt.py pack_meta
    return ((score + 32768) << 10) | (depth << 2) | flag


def test_check_tt_rows_accepts_storable_rows():
    rows = [[7, 12345, _meta(150, 8, 1), 1028, 3],
            [9, 54321, _meta(-29999, 30, 2), 514, 3]]
    assert sanitize.check_tt_rows(rows, "t::tt", stride=1) == 2


def test_check_tt_rows_skips_empty_slots_and_handles_4col():
    rows = [[0, 0, 0, 0],
            [12345, _meta(0, 1, 0), 66, 1]]
    assert sanitize.check_tt_rows(rows, "t::tt", stride=1) == 1


def test_check_tt_rows_rejects_flag3_and_overrange_score():
    bad_flag = [[7, 1, _meta(0, 1, 3), 66, 1]]
    with pytest.raises(SanitizeError, match="flag=3"):
        sanitize.check_tt_rows(bad_flag, "t::tt", stride=1)
    bad_score = [[7, 1, _meta(31000, 1, 1), 66, 1]]
    with pytest.raises(SanitizeError, match="score=31000"):
        sanitize.check_tt_rows(bad_score, "t::tt", stride=1)


def test_check_tt_rows_sampling_stride():
    good = [7, 1, _meta(10, 4, 1), 66, 1]
    bad = [8, 1, _meta(0, 1, 3), 66, 1]
    rows = [good] * 130
    rows[65] = bad  # off-stride with the default 64: not sampled
    assert sanitize.check_tt_rows(rows, "t::tt") == 3  # 0, 64, 128
    with pytest.raises(SanitizeError):
        sanitize.check_tt_rows(rows, "t::tt", stride=1)


def test_ttwarm_store_checks_rows_when_armed(tmp_path):
    from fishnet_tpu.cache.ttwarm import TTWarmStore

    store = TTWarmStore(directory=str(tmp_path))
    store._sanitize = True
    good = [[7, 12345, _meta(150, 8, 1), 1028, 3]]
    store.record(10, "abcd", good)
    assert store.lookup(10, "abcd") == good

    bad = [[9, 1, _meta(0, 1, 3), 66, 1]]
    with pytest.raises(SanitizeError, match="TTWarmStore.record"):
        store.record(10, "efgh", bad)

    # a bad slice that reached disk (written by an unarmed process,
    # hashes fine) trips the LOOKUP check in an armed one
    unarmed = TTWarmStore(directory=str(tmp_path))
    assert unarmed._sanitize is False
    unarmed.record(10, "efgh", bad)
    fresh = TTWarmStore(directory=str(tmp_path))
    fresh._sanitize = True
    with pytest.raises(SanitizeError, match="TTWarmStore.lookup"):
        fresh.lookup(10, "efgh")
