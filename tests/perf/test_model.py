"""resolve_fastest + preset_blocks semantics (repro.perf.model).

The load-bearing claims: no preset / stale fingerprint -> bitwise-identical
to ``resolve_for``; a preset can change scheme/route but can NEVER loosen
the accuracy tier; the fused block-table consult is injectable and
bitwise-neutral."""
import os

import numpy as np
import pytest

from repro.obs.metrics import shape_bucket
from repro.perf.fingerprint import hardware_fingerprint
from repro.perf.model import (PerfModel, PresetEntry, clear_default_model,
                              preset_blocks, resolve_fastest,
                              set_default_model)
from repro.precision import parse_policy


@pytest.fixture(autouse=True)
def _isolate_default_model():
    """Tests inject models via set_default_model; always restore the scan."""
    yield
    clear_default_model()


@pytest.fixture
def operands(rng):
    return rng.standard_normal((64, 64)), rng.standard_normal((64, 64))


BUCKET = shape_bucket(64, 64, 64)


def fresh_model(entries):
    return PerfModel(entries, {"fingerprint": hardware_fingerprint()})


def entry(spec, *, tier=1e-8, wall=0.001, rel_err=None, bucket=BUCKET,
          backend=None, blocks=None, blocks_key=""):
    import jax
    return PresetEntry(
        shape_bucket=bucket,
        backend=backend if backend is not None else jax.default_backend(),
        tier=tier, spec=spec, wall_seconds=wall,
        rel_err=rel_err if rel_err is not None else tier / 10,
        blocks=blocks, blocks_key=blocks_key)


class TestFallbackSemantics:
    def test_no_model_identical_to_resolve_for(self, operands):
        a, b = operands
        set_default_model(None)
        pol = parse_policy("ozaki2-fp8/fast")
        assert resolve_fastest(a, b, 1e-8, policy=pol) == \
            pol.resolve_for(a, b, 1e-8)

    def test_stale_fingerprint_identical_to_resolve_for(self, operands):
        a, b = operands
        stale = PerfModel(
            [entry("ozaki2-int8/fast@20")],
            {"fingerprint": {"jax_platform": "not-this-machine"}})
        pol = parse_policy("ozaki2-fp8/fast")
        assert resolve_fastest(a, b, 1e-8, policy=pol, model=stale) == \
            pol.resolve_for(a, b, 1e-8)

    def test_no_matching_bucket_falls_back(self, operands):
        a, b = operands
        model = fresh_model([entry("ozaki2-int8/fast@20",
                                   bucket=shape_bucket(4096, 4096, 4096))])
        pol = parse_policy("ozaki2-fp8/fast")
        assert resolve_fastest(a, b, 1e-8, policy=pol, model=model) == \
            pol.resolve_for(a, b, 1e-8)

    def test_no_tight_enough_tier_falls_back(self, operands):
        a, b = operands
        model = fresh_model([entry("ozaki2-int8/fast@20", tier=1e-4)])
        pol = parse_policy("ozaki2-fp8/fast")
        # target 1e-8 is tighter than the preset's guaranteed 1e-4 tier
        assert resolve_fastest(a, b, 1e-8, policy=pol, model=model) == \
            pol.resolve_for(a, b, 1e-8)

    def test_default_policy_when_no_context(self, operands):
        a, b = operands
        set_default_model(None)
        got = resolve_fastest(a, b, 1e-8)
        assert got == parse_policy("ozaki2-fp8/fast").resolve_for(a, b, 1e-8)


class TestPresetBacked:
    def test_preset_breaks_tie_toward_measured_winner(self, operands):
        a, b = operands
        model = fresh_model([entry("ozaki2-int8/fast@20+pallas", tier=1e-9)])
        got = resolve_fastest(a, b, 1e-8, policy="ozaki2-fp8/fast",
                              model=model)
        assert got.scheme == "ozaki2-int8"
        assert got.backend == "pallas"
        # moduli = max(preset's count, the floor under the winner's scheme)
        floor = parse_policy(
            "ozaki2-int8/fast+pallas").resolve_for(a, b, 1e-8).num_moduli
        assert got.num_moduli == max(20, floor)

    def test_preset_never_loosens_accuracy(self, operands):
        a, b = operands
        # a preset claiming a 2-modulus winner: the resolver floor for the
        # SAME scheme/mode must win, so the result cannot be less accurate
        # than resolve_for promises
        model = fresh_model([entry("ozaki2-fp8/fast@2+pallas", tier=1e-7)])
        got = resolve_fastest(a, b, 1e-6, policy="ozaki2-fp8/fast",
                              model=model)
        floor = parse_policy(
            "ozaki2-fp8/fast+pallas").resolve_for(a, b, 1e-6).num_moduli
        assert got.num_moduli == max(2, floor)
        assert got.num_moduli >= floor

    def test_injected_default_model_used(self, operands):
        a, b = operands
        set_default_model(
            fresh_model([entry("ozaki2-int8/fast@20+pallas", tier=1e-9)]))
        got = resolve_fastest(a, b, 1e-8, policy="ozaki2-fp8/fast")
        assert got.scheme == "ozaki2-int8"


class TestLookup:
    def test_tie_break_deterministic(self):
        import jax
        backend = jax.default_backend()
        e1 = entry("ozaki2-int8/fast@8", wall=0.001, tier=1e-9)
        e2 = entry("ozaki2-fp8/fast@8", wall=0.001, tier=1e-9)
        model = fresh_model([e1, e2])
        got = model.lookup(64, 64, 64, backend, 1e-8)
        # identical wall + tier: lexicographically smaller spec wins
        assert got.spec == "ozaki2-fp8/fast@8"

    def test_fastest_meeting_tier_wins(self):
        import jax
        backend = jax.default_backend()
        model = fresh_model([
            entry("ozaki2-fp8/fast@6", wall=0.002, tier=1e-9),
            entry("ozaki2-int8/fast@8", wall=0.001, tier=1e-9),
            entry("ozaki2-fp8/fast@4", wall=0.0005, tier=1e-4),  # too loose
        ])
        got = model.lookup(64, 64, 64, backend, 1e-8)
        assert got.spec == "ozaki2-int8/fast@8"


class TestPresetBlocks:
    def mk(self, **kw):
        return fresh_model([entry("ozaki2-fp8/fast@4+pallas", tier=1e-4,
                                  blocks=(32, 64, 32),
                                  blocks_key="interpret", **kw)])

    def test_exact_match(self):
        assert preset_blocks("fp8-hybrid", 4, "interpret",
                             self.mk()) == (32, 64, 32)

    def test_moduli_count_must_match_exactly(self):
        assert preset_blocks("fp8-hybrid", 6, "interpret", self.mk()) is None

    def test_blocks_key_must_match(self):
        assert preset_blocks("fp8-hybrid", 4, "tpu", self.mk()) is None

    def test_family_must_match(self):
        assert preset_blocks("int8", 4, "interpret", self.mk()) is None

    def test_stale_model_returns_none(self):
        stale = PerfModel(
            [entry("ozaki2-fp8/fast@4+pallas", tier=1e-4,
                   blocks=(32, 64, 32), blocks_key="interpret")],
            {"fingerprint": {"jax_platform": "elsewhere"}})
        assert preset_blocks("fp8-hybrid", 4, "interpret", stale) is None

    def test_faster_entry_wins(self):
        model = fresh_model([
            entry("ozaki2-fp8/fast@4+pallas", tier=1e-4, wall=0.002,
                  blocks=(64, 64, 64), blocks_key="interpret"),
            entry("ozaki2-fp8/accurate@4+pallas", tier=1e-4, wall=0.001,
                  blocks=(32, 64, 32), blocks_key="interpret"),
        ])
        assert preset_blocks("fp8-hybrid", 4, "interpret",
                             model) == (32, 64, 32)


class TestSelectBlocksIntegration:
    def test_precedence_override_env_preset_table(self, monkeypatch):
        from repro.kernels import select_blocks
        from repro.kernels.fused.ops import BLOCKS_ENV

        monkeypatch.delenv(BLOCKS_ENV, raising=False)
        set_default_model(None)
        table = select_blocks("fp8-hybrid", 4, True)

        set_default_model(fresh_model([
            entry("ozaki2-fp8/fast@4+pallas", tier=1e-4,
                  blocks=(32, 64, 32), blocks_key="interpret")]))
        assert select_blocks("fp8-hybrid", 4, True) == (32, 64, 32)
        assert select_blocks("fp8-hybrid", 4, True) != table or \
            table == (32, 64, 32)
        # env override still beats the preset
        monkeypatch.setenv(BLOCKS_ENV, "16,32,16")
        assert select_blocks("fp8-hybrid", 4, True) == (16, 32, 16)
        # explicit kwarg beats everything
        assert select_blocks("fp8-hybrid", 4, True, (8, 16, 8)) == (8, 16, 8)
        monkeypatch.delenv(BLOCKS_ENV)
        # the @4 preset does NOT leak onto other modulus counts: the static
        # table row answers for @12 (tests/kernels pins this value too)
        assert select_blocks("fp8-hybrid", 12, True) == (64, 128, 64)
        set_default_model(None)
        assert select_blocks("fp8-hybrid", 4, True) == table

    def test_preset_tiling_is_bitwise_neutral(self, rng, monkeypatch):
        """Acceptance: consulting a preset tiling changes schedule only —
        the fused GEMM result stays bitwise-identical to the table tiling."""
        from repro.kernels import ozmm_pallas_fused
        from repro.kernels.fused.ops import BLOCKS_ENV

        monkeypatch.delenv(BLOCKS_ENV, raising=False)
        a = rng.standard_normal((48, 40))
        b = rng.standard_normal((40, 56))
        set_default_model(None)
        ref = np.asarray(ozmm_pallas_fused(a, b, family="fp8-hybrid",
                                           num_moduli=4, mode="fast",
                                           interpret=True))
        set_default_model(fresh_model([
            entry("ozaki2-fp8/fast@4+pallas", tier=1e-4,
                  blocks=(32, 64, 32), blocks_key="interpret")]))
        out = np.asarray(ozmm_pallas_fused(a, b, family="fp8-hybrid",
                                           num_moduli=4, mode="fast",
                                           interpret=True))
        assert np.array_equal(out, ref)

    def test_broken_preset_never_breaks_select_blocks(self):
        from repro.kernels.fused.ops import _preset_blocks

        class Exploding:
            @property
            def entries(self):
                raise RuntimeError("corrupt")

            def fresh(self, *_):
                return True

        # Corrupt preset FILES are skipped by default_model (test_presets);
        # a model that fails while it is consulted is a bug and must raise
        # instead of silently falling back to the static table.
        set_default_model(Exploding())
        with pytest.raises(RuntimeError, match="corrupt"):
            _preset_blocks("fp8-hybrid", 4, "interpret")
