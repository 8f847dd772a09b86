"""chip_smoke.py's phases at SF0.01/0.02 on the CPU: the front door, the
three queries held to the NumPy reference cold (at once) and warm, a
deliberately wrong reference failing, ``main()`` refusing to pass without a TPU, the mesh phase on
four virtual devices, and the compile-cache helper. The chip itself is
driven by ``python chip_smoke.py`` through the builder's tool, never by
pytest."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke as S
import presto_tpu

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def door():
    """Q6 and Q1 at SF0.02 and Q3 cut to SF0.01 — the two-catalog form
    main() runs at SF10 / SF0.1 — answered cold, all at once."""
    door, wants = S._open_door(0.02, None, 0.01, ("q3",))
    try:
        sts = {q: S.Statement(door, q, wants[q][1], wants[q][0])
               for q in S.QUERIES}
        cold = S.cold_pass(list(sts.values()))
        yield door, sts, cold
    finally:
        door.close()


def test_cold_pass_answers_every_statement(door):
    d, sts, cold = door
    assert sorted(cold) == sorted(S.QUERIES)
    assert all(v > 0 for v in cold.values())
    assert {q: st.client.catalog for q, st in sts.items()} == {
        "q6": "tpch", "q1": "tpch", "q3": "tpch_cut"}
    assert ("from tpch_cut.default.customer, tpch_cut.default.orders, "
            "tpch_cut.default.lineitem") in sts["q3"].sql
    assert "from lineitem" in sts["q1"].sql
    assert d.conns["tpch"].sf == 0.02 and d.conns["tpch_cut"].sf == 0.01
    assert len(sts["q1"].want) == 4 and len(sts["q3"].want) == 10
    assert S.compile_log().seconds() > 0


@pytest.mark.parametrize("name", S.QUERIES)
def test_query_phase_matches_reference(door, name):
    """Warm and profiled through POST /v1/statement, each answer equal
    to the NumPy reference, the device shown to have worked."""
    rec = S.query_phase(door[1][name])
    assert min(rec["invocations"]) > 0 and rec["device_s"] > 0
    if name == "q1":
        assert rec["paths"].get("agg_dense_path_selected_total") == 2
    if name == "q3":
        assert rec["paths"].get("agg_sort_path_selected_total") == 2
        # orders gets a direct table; what BUILDING leaves of SF0.01's
        # customers (~300) is compared key by key, no table filled
        assert rec["paths"].get(
            "join_strategy_selected_total.direct.replicated") == 2
        assert rec["paths"].get(
            "join_strategy_selected_total.compare.replicated") == 2
        assert "lookup_join" in rec["executables"]
        assert "lookup_join_pallas" not in rec["executables"]
    assert S.resident_platforms() == {"cpu"}


def _wrong(name, want):
    """The reference, off by more than the tolerance in one DOUBLE, and
    off by one in one exact column."""
    if name == "q6":
        return [want * (1 + 1e-4)]
    rows = [list(r) for r in want]
    exact = [list(r) for r in want]
    if name == "q1":
        rows[0][4] *= 1 + 1e-4
        exact[-1][9] += 1
    else:
        rows[0][1] *= 1 + 1e-4
        exact[-1][0] += 1
    return [[tuple(r) for r in rows], [tuple(r) for r in exact]]


@pytest.mark.parametrize("name", S.QUERIES)
def test_wrong_reference_fails_the_phase(door, name):
    d, sts, _ = door
    for bad in _wrong(name, sts[name].want):
        st = S.Statement(d, name, bad, sts[name].client.catalog)
        with pytest.raises(S.SmokeFailure, match=name):
            S.cold_pass([st])
        with pytest.raises(S.SmokeFailure, match=name):
            S.query_phase(st)


def test_failed_query_raises(door):
    from presto_tpu.client import QueryFailed
    with pytest.raises(QueryFailed):
        S.run_statement(door[0].client(),
                        "select no_such_column from lineitem")


def test_main_exits_nonzero_without_a_tpu(capsys):
    assert jax.devices()[0].platform == "cpu"
    for argv in ([], ["--chips", "4"]):
        assert S.main(argv) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_script_alone_exits_nonzero(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo, the script fails and prints no result."""
    (tmp_path / "chip_smoke.py").write_text(
        open(os.path.join(_REPO, "chip_smoke.py")).read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_mesh_phase_on_four_virtual_devices():
    """--chips 4's phase: Q1 and Q3 meshed over four devices, held to the
    single-device answers and the reference, scan columns spread over
    four distinct devices."""
    assert len(jax.devices()) >= 4
    recs = S.smoke_mesh(0.01, 4)
    assert [r["query"] for r in recs] == [
        "q1/mesh4", "q1/single", "q3/mesh4", "q3/single"]
    assert all(r["cold_s"] > 0 and r["warm_s"] > 0 for r in recs)


# ---------------------------------------------------------------------------
# the compile-cache helper
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_set_sets_no_directory(monkeypatch,
                                                 cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", "/set/by/jax/itself")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/from/outside")
    presto_tpu.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == "/set/by/jax/itself"


def test_compile_cache_env_unset_uses_fixed_path(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    presto_tpu.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        _REPO, ".jax_cache")
    presto_tpu.enable_compile_cache()          # idempotent
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        _REPO, ".jax_cache")


def test_tests_keep_their_own_cache_directory():
    """tests/conftest.py places the CPU entries from outside, through
    the variable the helper honours."""
    placed = os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert placed and placed != os.path.join(_REPO, ".jax_cache")
