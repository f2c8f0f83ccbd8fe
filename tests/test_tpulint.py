"""tpulint contract (ISSUE 7): every rule fires on its fixture and is
silenced by a reasoned suppression; the repo itself lints clean; the
serving shape manifest round-trips and its key space is closed; the
sync-point sanitizer measures the decode hot path.

Rule coverage is completeness-checked: adding a rule to
``tools/tpulint/rules.py`` without a fixture pair here fails
``test_every_rule_has_a_fixture``.
"""
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.tpulint import RULES, lint_paths, lint_source  # noqa: E402


def _active(src):
    return lint_source(src, "<fixture>").active


def _suppressed(src):
    return lint_source(src, "<fixture>").suppressed


# ---------------------------------------------------------------------------
# one fixture pair per rule: (positive snippet, suppressed snippet).
# The suppressed variant is the SAME hazard with a reasoned per-line
# disable — it must produce zero active findings but still record the
# suppressed finding (suppression is visible, never silent deletion).

FIXTURES = {
    "traced-branch": (
        "from paddle.jit import to_static\n"
        "@to_static\n"
        "def f(x):\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n",
        "from paddle.jit import to_static\n"
        "@to_static\n"
        "def f(x):\n"
        "    # tpulint: disable=traced-branch -- fixture: intentional\n"
        "    if x > 0:\n"
        "        return x\n"
        "    return -x\n",
    ),
    "traced-coerce": (
        "@to_static\n"
        "def f(x):\n"
        "    return float(x) * 2\n",
        "@to_static\n"
        "def f(x):\n"
        "    return float(x) * 2  # tpulint: disable=traced-coerce -- fixture: intentional\n",
    ),
    "mutable-global": (
        "CACHE = {}\n"
        "@to_static\n"
        "def f(x):\n"
        "    return x + CACHE.get('bias', 0)\n",
        "CACHE = {}\n"
        "@to_static\n"
        "def f(x):\n"
        "    # tpulint: disable=mutable-global -- fixture: intentional\n"
        "    return x + CACHE.get('bias', 0)\n",
    ),
    "nonhashable-static": (
        "@to_static\n"
        "def f(x, opts=[]):\n"
        "    return x\n",
        "@to_static\n"
        "def f(x, opts=[]):  # tpulint: disable=nonhashable-static -- fixture: intentional\n"
        "    return x\n",
    ),
    "traced-format": (
        "@to_static\n"
        "def f(x):\n"
        "    print('x is', x)\n"
        "    return x\n",
        "@to_static\n"
        "def f(x):\n"
        "    print('x is', x)  # tpulint: disable=traced-format -- fixture: intentional\n"
        "    return x\n",
    ),
    "host-sync": (
        "# tpulint: hot-path\n"
        "def step(t):\n"
        "    return t.numpy()\n",
        "# tpulint: hot-path\n"
        "def step(t):\n"
        "    return t.numpy()  # tpulint: disable=host-sync -- fixture: intentional\n",
    ),
}


def test_every_rule_has_a_fixture():
    assert set(FIXTURES) == set(RULES), (
        "every registered rule needs a (positive, suppressed) fixture "
        f"pair; missing: {set(RULES) - set(FIXTURES)}, stale: "
        f"{set(FIXTURES) - set(RULES)}")


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fires_on_fixture(rule):
    positive, _ = FIXTURES[rule]
    hits = [f for f in _active(positive) if f.rule == rule]
    assert hits, f"{rule} did not fire on its positive fixture"
    f = hits[0]
    assert f.code == RULES[rule].code
    assert f.line > 0 and f.message


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_reasoned_suppression_silences_rule(rule):
    _, suppressed = FIXTURES[rule]
    res = lint_source(suppressed, "<fixture>")
    assert not res.active, (
        f"{rule}: reasoned suppression left active findings: "
        f"{[f.format() for f in res.active]}")
    sup = [f for f in res.suppressed if f.rule == rule]
    assert sup and sup[0].reason == "fixture: intentional", (
        f"{rule}: the suppressed finding must stay visible with its "
        "reason")


# -- suppression policing ---------------------------------------------------

def test_reasonless_suppression_is_a_finding_and_suppresses_nothing():
    src = ("@to_static\n"
           "def f(x):\n"
           "    return float(x)  # tpulint: disable=traced-coerce\n")
    active = _active(src)
    rules = {f.rule for f in active}
    assert "bad-suppression" in rules     # the reasonless pragma itself
    assert "traced-coerce" in rules       # ...and it silenced NOTHING


def test_unknown_rule_suppression_is_a_finding():
    src = ("@to_static\n"
           "def f(x):\n"
           "    return x  # tpulint: disable=no-such-rule -- typo'd\n")
    assert any(f.rule == "bad-suppression" and "unknown" in f.message
               for f in _active(src))


def test_bad_suppression_cannot_be_suppressed():
    src = "x = 1  # tpulint: disable=bad-suppression -- nice try\n"
    assert any(f.rule == "bad-suppression" for f in _active(src))


def test_suppression_by_tpl_code_works():
    # findings print as `TPL102(traced-coerce)` — the code a developer
    # copies from the output must suppress, same as the name
    src = ("@to_static\n"
           "def f(x):\n"
           "    return float(x)  # tpulint: disable=TPL102 -- code-form suppression\n")
    res = lint_source(src, "<fixture>")
    assert not res.active, [f.format() for f in res.active]
    assert [f.rule for f in res.suppressed] == ["traced-coerce"]


def test_suppression_on_comment_line_above_covers_next_line():
    src = ("@to_static\n"
           "def f(x):\n"
           "    # tpulint: disable=traced-coerce -- long line needs the comment above\n"
           "    return float(x)\n")
    res = lint_source(src, "<fixture>")
    assert not res.active and len(res.suppressed) == 1


def test_trailing_comment_of_previous_stmt_does_not_leak_downward():
    # a suppression at the END of a code line covers THAT line only
    src = ("@to_static\n"
           "def f(x):\n"
           "    a = float(x)  # tpulint: disable=traced-coerce -- this line only\n"
           "    return float(x)\n")
    assert any(f.rule == "traced-coerce" for f in _active(src))


def test_parse_error_is_reported_not_raised():
    res = lint_source("def broken(:\n", "<fixture>")
    assert any(f.rule == "parse-error" for f in res.findings)


# -- analysis precision (the false-positive classes PR 7 triaged) -----------

def test_static_metadata_branches_are_not_flagged():
    src = ("@to_static\n"
           "def f(x):\n"
           "    if x.shape[0] > 4:\n"
           "        return x\n"
           "    if len(x.shape) == 2 and isinstance(x, object):\n"
           "        return x\n"
           "    if x is None:\n"
           "        return x\n"
           "    return x\n")
    assert not _active(src)


def test_wrapped_name_marks_function_scope_aware():
    # `jax.jit(run)` marks the `run` in ITS scope; an unrelated method
    # of the same name elsewhere stays out of lint scope
    src = ("def build():\n"
           "    def run(x):\n"
           "        return float(x)\n"
           "    import jax\n"
           "    return jax.jit(run)\n"
           "class Executor:\n"
           "    def run(self, x):\n"
           "        return float(x)\n")
    hits = [f.line for f in _active(src) if f.rule == "traced-coerce"]
    assert hits == [3], hits


def test_zip_loop_taint_is_element_wise():
    # zipping concrete metadata with traced arrays must not taint the
    # metadata elements
    src = ("@to_static\n"
           "def f(xs):\n"
           "    locs = [(0, 1), (1, 2)]\n"
           "    for (kind, idx), arr in zip(locs, xs):\n"
           "        if kind:\n"
           "            pass\n"
           "    return xs\n")
    assert not [f for f in _active(src) if f.rule == "traced-branch"]


def test_walrus_bound_traced_values_do_not_escape():
    # `(y := x + 1)` carries taint into the test AND binds y traced
    src = ("@to_static\n"
           "def f(x):\n"
           "    if (y := x + 1) > 0:\n"
           "        return float(y)\n"
           "    return x\n")
    rules = {f.rule for f in _active(src)}
    assert "traced-branch" in rules   # the walrus-carrying test itself
    assert "traced-coerce" in rules   # ...and later uses of its target


def test_hot_path_requires_marker():
    src = "def step(t):\n    return t.numpy()\n"
    assert not _active(src)   # unmarked host fn: no hot-path findings


def test_hot_path_marker_survives_decorators():
    # decorators sit between the marker and the `def` line; the marker
    # must keep working when a marked function gains one
    src = ("# tpulint: hot-path\n"
           "@staticmethod\n"
           "def step(t):\n"
           "    return t.numpy()\n")
    assert any(f.rule == "host-sync" for f in _active(src))


# -- the repo itself --------------------------------------------------------

def test_repo_lints_clean_with_zero_suppressions():
    """Since ISSUE 11 moved sampling on-device, the serving hot path
    needs NO host-sync justification at all: the two engine suppressions
    PR 7 recorded (the per-step and per-admission sampling pulls) are
    gone, and any suppression creeping back in would mask a real decode
    host-transfer regression."""
    res = lint_paths([os.path.join(REPO, "paddle_tpu")])
    assert res.files > 100          # the walk actually saw the tree
    assert not res.active, "\n".join(f.format() for f in res.active)
    assert not res.suppressed, (
        "the hot path should need zero suppressions since on-device "
        "sampling: " + "\n".join(f.format() for f in res.suppressed))


# -- shape manifest ---------------------------------------------------------

class TestShapeManifest:
    @pytest.fixture(scope="class")
    def fresh(self):
        from tools.tpulint.shape_closure import build_manifest

        # build_manifest raises AssertionError on any closure escape,
        # so constructing it IS the closure proof
        return build_manifest()

    def test_committed_manifest_matches_fresh_enumeration(self, fresh):
        from tools.tpulint.shape_closure import (DEFAULT_MANIFEST,
                                                 diff_manifests)

        with open(DEFAULT_MANIFEST) as f:
            committed = json.load(f)
        assert diff_manifests(committed, fresh) == []
        assert committed["digest"] == fresh["digest"]

    def test_key_space_is_buckets_plus_one_per_layout(self, fresh):
        # plain and tenancy engines: one prefill per bucket + ONE decode;
        # the speculative engine replaces decode with one draft prefill
        # per bucket + ONE draft decode + ONE verify (ISSUE 15)
        for variant, sec in fresh["configs"].items():
            nb = len(sec["buckets"])
            want = 2 * nb + 2 if variant == "speculative" else nb + 1
            assert sec["programs"] == want, variant
            assert sec["closure_probe"]["escapes"] == 0

    def test_no_program_moved_when_the_contiguous_section_went(self, fresh):
        """PR 28 deleted the contiguous engine and its section: every
        other entry is what PR 27's manifest held — cache key, lifted
        state inputs, writes — so no paged, speculative or tenancy
        program changed.  (Re-pin deliberately when one does.)"""
        prefill = ["8ab0ea60f44d916e", "f43a9a2797c18d5a",
                   "ef542b13f3ada239", "276a4b7469556f87"]
        draft = ["6eff3b93d510e624", "7b2b604ad9f0f2f8",
                 "6615f12fbf7c5e97", "8fd8b55eebc4a9c6"]
        buckets = (8, 16, 32, 64)

        def plain(n_state, n_writes):
            return {**{f"prefill[b={b}]": (k, n_state, n_writes)
                       for b, k in zip(buckets, prefill)},
                    "decode": ("a72890e51ed409ff", n_state, n_writes)}

        spec = plain(39, 7)
        del spec["decode"]
        spec.update({f"draft_prefill[b={b}]": (k, 33, 4)
                     for b, k in zip(buckets, draft)})
        spec["draft_decode"] = ("bac3a9a1fbbb88c4", 37, 6)
        spec["verify"] = ("f1b5bda326b4336a", 75, 11)
        at_pr27 = {"paged": plain(39, 7), "speculative": spec,
                   "tenancy": plain(60, 8)}
        got = {variant: {name: (e["key_sha256"], e["n_state_inputs"],
                                e["n_writes"])
                         for name, e in sec["entries"].items()}
               for variant, sec in fresh["configs"].items()}
        assert got == at_pr27
        assert fresh["fleet"]["programs_per_replica"] == {"paged": 5}
        assert {k: v for shape in fresh["sharded"]["mesh_shapes"].values()
                for k, v in shape.items()} == \
            {"paged": {"programs": 5, "keys_equal_unsharded": True}}

    def test_entries_are_fully_specified(self, fresh):
        for sec in fresh["configs"].values():
            for name, e in sec["entries"].items():
                assert e["args"] and e["out"] and e["key_sha256"], name
                assert e["n_state_inputs"] > 0, name

    def test_fleet_multiplies_executables_not_keys(self, fresh):
        fl = fresh["fleet"]
        assert fl["total_executables"] == fl["replicas"] * sum(
            fl["programs_per_replica"].values())

    def test_diff_catches_non_entry_drift(self, fresh):
        # the proof is more than the entries: a hand-edited fleet
        # section or engine config must fail the diff too
        from tools.tpulint.shape_closure import diff_manifests

        stale = json.loads(json.dumps(fresh))
        stale["fleet"]["replicas"] = 99
        assert any("fleet" in p for p in diff_manifests(stale, fresh))

        stale = json.loads(json.dumps(fresh))
        stale["configs"]["paged"]["engine"]["block_size"] = 4
        assert any("config section drifted" in p
                   for p in diff_manifests(stale, fresh))


# -- sync-point sanitizer ---------------------------------------------------

class TestSanitizer:
    @pytest.fixture()
    def eager_engine(self):
        import paddle_tpu as paddle
        from paddle_tpu.models import gpt_tiny, GPTForCausalLM
        from paddle_tpu.serving import Engine

        paddle.jit.enable_to_static(False)
        try:
            yield Engine(GPTForCausalLM(gpt_tiny()), num_slots=2,
                         max_seq=32, min_bucket=8, block_size=8)
        finally:
            paddle.jit.enable_to_static(True)

    def test_counts_zero_transfers_per_decode_step(self, eager_engine):
        """ISSUE 11: on-device sampling emptied the decode window — the
        PR 7 baseline was exactly 1.0 (the host-side sampling logits
        pull); now the dispatch performs no framework-level d2h at all
        (the stream-delivery token pull happens after the window, by
        design)."""
        from paddle_tpu.serving import SyncSanitizer

        eng = eager_engine
        eng.sanitizer = SyncSanitizer()
        eng.generate([[1, 2, 3], [4, 5]], max_new_tokens=4)
        rep = eng.stats()["sanitizer"]
        assert rep["decode_steps"] >= 3
        assert rep["per_decode_step"] == 0.0, rep
        assert rep["host_transfers"] == 0 and rep["by_site"] == {}, rep

    def test_unarmed_engine_reports_no_sanitizer(self, eager_engine):
        assert eager_engine.sanitizer is None
        assert "sanitizer" not in eager_engine.stats()

    def test_window_is_reentrancy_safe(self):
        from paddle_tpu.core import tensor as tensor_mod
        from paddle_tpu.serving import SyncSanitizer

        san = SyncSanitizer()
        with san.decode_window():
            assert tensor_mod._sync_hook == san._on_sync
            with san.decode_window():
                pass
            # inner exit must not uninstall the outer window's hook
            assert tensor_mod._sync_hook == san._on_sync
        # steps are counted by note_step (a compiled step actually ran),
        # never by window entry — aborted windows don't dilute the baseline
        assert san.decode_steps == 0
        assert tensor_mod._sync_hook is None   # uninstalled on exit

    def test_attribution_skips_tensor_plumbing(self):
        import numpy as np
        from paddle_tpu.core.tensor import to_tensor
        from paddle_tpu.serving import SyncSanitizer

        san = SyncSanitizer()
        t = to_tensor(np.ones((2, 2), dtype=np.float32))
        with san.decode_window():
            t.numpy()
            t.tolist()
            bool(t.sum() > 0)
        assert san.host_transfers == 3
        for site in san.by_site:
            assert "core/tensor.py" not in site, san.by_site
            assert "test_tpulint" in site, san.by_site

    def test_from_env(self, monkeypatch):
        from paddle_tpu.serving import SyncSanitizer

        monkeypatch.delenv("PADDLE_TPU_SANITIZE", raising=False)
        assert SyncSanitizer.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_SANITIZE", "0")
        assert SyncSanitizer.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_SANITIZE", "1")
        san = SyncSanitizer.from_env()
        assert san is not None and not san.strict
        monkeypatch.setenv("PADDLE_TPU_SANITIZE", "strict")
        assert SyncSanitizer.from_env().strict
        monkeypatch.setenv("PADDLE_TPU_SANITIZE", "off")
        assert SyncSanitizer.from_env() is None
        monkeypatch.setenv("PADDLE_TPU_SANITIZE", "bogus")
        with pytest.raises(ValueError, match="PADDLE_TPU_SANITIZE"):
            SyncSanitizer.from_env()


# -- CLI --------------------------------------------------------------------

def test_cli_exit_codes(tmp_path):
    from tools.tpulint.__main__ import main

    bad = tmp_path / "bad.py"
    bad.write_text(FIXTURES["traced-branch"][0])
    good = tmp_path / "good.py"
    good.write_text(FIXTURES["traced-branch"][1])
    assert main([str(bad)]) == 1
    assert main([str(good), "--show-suppressed"]) == 0
    assert main(["--list-rules"]) == 0
    assert main(["--no-such-flag"]) == 2


def test_shape_closure_cli_rejects_bad_arguments():
    from tools.tpulint.shape_closure import main

    assert main(["--path"]) == 2      # value forgotten
    # a typo'd --write must not fall through to check mode and print OK
    assert main(["--wrte"]) == 2
