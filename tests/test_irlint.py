"""irlint — IR-level static analysis (tools/irlint/).

Unit coverage per rule (positive/negative on tiny synthetic programs),
StableHLO donation/sharding parsing incl. the pruned-arg alignment,
suppression semantics at registration sites, the frontend gate, and the
acceptance pins: the full default manifest lowers + lints CLEAN against
the empty baseline, the donation audit accounts every donated leaf, the
``seist_l`` bf16 train step's matmul-FLOPs coverage is >= 0.9, and the bf16 policy reaches the head matmuls of ALL FIVE
task-head families (dpk/pmp/emg/baz/dis), not just the trunk.
"""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from tools.irlint import ir
from tools.irlint.manifest import (
    ProgramInfo,
    ProgramSpec,
    SiteRef,
    default_manifest,
    train_programs,
    group_programs,
    stream_program,
    variant_structs,
)
from tools.irlint.rules import (
    RULES_BY_NAME,
    check_donation,
    check_padding,
    check_precision,
    check_replication,
    lint_programs,
)
from tools.irlint.__main__ import apply_site_suppressions, main as irlint_main

# Cheap unit classes carry the smoke mark individually; the manifest /
# acceptance classes trace real seist programs (tens of seconds) and must
# NOT ride into the instrumented smoke lanes (lockgraph, --tracer-leaks).
smoke = pytest.mark.smoke

_SITE = SiteRef(file="tests/test_irlint.py", line=1, text='"""irlint')


def _spec(fn, args, **kw):
    defaults = dict(
        key="test/prog", kind="train", site=_SITE, fn=fn, args=tuple(args)
    )
    defaults.update(kw)
    return ProgramSpec(**defaults)


def _f32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.float32)


def _bf16(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


# ------------------------------------------------------- stablehlo parsing
@smoke
class TestDonationParsing:
    def test_plain_jit_alias_detected(self):
        def f(s, x):
            return s + x.sum(), x * 2

        low = jax.jit(f, donate_argnums=(0,)).lower(_f32(), _f32(4, 4))
        audit = ir.donation_audit(low.as_text(), (_f32(), _f32(4, 4)), (0,))
        assert audit["donated_leaves"] == 1
        assert audit["aliased_leaves"] == 1
        assert audit["unaliased"] == []
        assert audit["stray_aliases"] == []

    def test_unaliasable_donation_flagged(self):
        # arg0 (scalar) matches no output shape: the lowering drops the
        # donation ("not usable") — the audit must surface it.
        def g(s, x):
            return x * 2.0

        low = jax.jit(g, donate_argnums=(0,)).lower(_f32(), _f32(4, 4))
        audit = ir.donation_audit(low.as_text(), (_f32(), _f32(4, 4)), (0,))
        assert audit["aliased_leaves"] == 0
        assert len(audit["unaliased"]) == 1

    def test_mesh_lowering_defers_to_buffer_donor(self):
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:8]).reshape(8), ("data",)
        )
        repl = NamedSharding(mesh, P())

        def f(s, x):
            return s + x.sum(), x * 2

        low = jax.jit(
            f, donate_argnums=(0,), in_shardings=(repl, repl)
        ).lower(_f32(), _f32(8, 4))
        audit = ir.donation_audit(low.as_text(), (_f32(), _f32(8, 4)), (0,))
        # Sharded lowerings mark jax.buffer_donor and let XLA pair the
        # buffers at compile time — "deferred", neither aliased nor lost.
        assert audit["deferred_leaves"] == 1
        assert audit["unaliased"] == []

    def test_pruned_arg_alignment(self):
        # jit prunes unused args (keep_unused=False default), shifting
        # every %argN after the hole; the audit must align via
        # kept_var_idx instead of assuming identity.
        def f(unused, s, x):
            return s + x.sum(), x * 2

        args = (_f32(3, 3), _f32(), _f32(4, 4))
        jitted = jax.jit(f, donate_argnums=(1,))
        low = jitted.lower(*args)
        kept = sorted(low._lowering.compile_args["kept_var_idx"])
        assert kept == [1, 2]  # arg0 pruned
        audit = ir.donation_audit(low.as_text(), args, (1,), kept=kept)
        assert audit["aliased_leaves"] == 1
        assert audit["unaliased"] == []
        # Without the alignment the donated scalar would be looked up at
        # %arg1 (which is x) — a false "unaliased" plus a stray alias.
        naive = ir.donation_audit(low.as_text(), args, (1,))
        assert naive["unaliased"] or naive["stray_aliases"]

    def test_pruned_donated_leaf_counted(self):
        def f(s, x):
            return x * 2

        args = (_f32(4, 4), _f32(4, 4))
        low = jax.jit(f, donate_argnums=(0,)).lower(*args)
        kept = sorted(low._lowering.compile_args["kept_var_idx"])
        audit = ir.donation_audit(low.as_text(), args, (0,), kept=kept)
        assert audit["pruned_leaves"] == 1
        assert audit["unaliased"] == []


@smoke
class TestShardingParsing:
    def _mesh(self):
        return jax.sharding.Mesh(
            np.array(jax.devices()[:8]).reshape(8), ("data",)
        )

    def test_sharded_data_arg_clean(self):
        mesh = self._mesh()

        def f(w, x):
            return (x @ w).sum()

        low = jax.jit(
            f,
            in_shardings=(
                NamedSharding(mesh, P()),
                NamedSharding(mesh, P("data")),
            ),
        ).lower(_f32(4, 4), _f32(8, 4))
        audit = ir.sharding_audit(
            low.as_text(), (_f32(4, 4), _f32(8, 4)), (1,)
        )
        assert audit["sharded_leaves"] == 1
        assert audit["replicated"] == []

    def test_replicated_data_arg_flagged(self):
        mesh = self._mesh()

        def f(w, x):
            return (x @ w).sum()

        low = jax.jit(
            f,
            in_shardings=(
                NamedSharding(mesh, P()),
                NamedSharding(mesh, P()),  # the bug: batch replicated
            ),
        ).lower(_f32(4, 4), _f32(8, 4))
        audit = ir.sharding_audit(
            low.as_text(), (_f32(4, 4), _f32(8, 4)), (1,)
        )
        assert audit["sharded_leaves"] == 0
        assert len(audit["replicated"]) == 1


# ------------------------------------------------------------ matmul table
@smoke
class TestMatmulTable:
    def test_exact_flops_and_coverage(self):
        def f(a, b):
            return a @ b

        jaxpr = jax.make_jaxpr(f)(_bf16(4, 8), _bf16(8, 16))
        table = ir.matmul_dtype_table(jaxpr)
        assert len(table) == 1
        assert table[0]["flops"] == 2 * 4 * 8 * 16
        cov = ir.matmul_coverage(table, "bfloat16")
        assert cov["coverage"] == 1.0

    def test_mixed_dtype_fraction(self):
        # f32 matmul has 4x the FLOPs of the bf16 one -> coverage 0.2.
        def f(a, b, c, d):
            return (a @ b).sum() + (c @ d).astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(f)(
            _f32(8, 8), _f32(8, 32), _bf16(8, 8), _bf16(8, 8)
        )
        cov = ir.matmul_coverage(
            ir.matmul_dtype_table(jaxpr), "bfloat16"
        )
        assert cov["coverage"] == pytest.approx(0.2)

    def test_scan_multiplies_trip_count(self):
        w = _bf16(8, 8)

        def f(w, xs):
            def body(c, x):
                return c, x @ w

            return jax.lax.scan(body, 0.0, xs)

        jaxpr = jax.make_jaxpr(f)(w, _bf16(3, 4, 8))
        table = ir.matmul_dtype_table(jaxpr)
        assert table[0]["count"] == 3
        assert table[0]["flops"] == 3 * 2 * 4 * 8 * 8

    def test_promotion_shows_mixed_operands(self):
        def f(a, b):
            return a @ b  # bf16 @ f32 promotes -> operands differ

        table = ir.matmul_dtype_table(
            jax.make_jaxpr(f)(_bf16(4, 8), _f32(8, 4))
        )
        assert ir.matmul_coverage(table, "bfloat16")["coverage"] < 1.0


@smoke
class TestHostTransfers:
    def test_callback_detected(self):
        def f(x):
            y = jax.pure_callback(
                lambda a: np.asarray(a), jax.ShapeDtypeStruct((4,), np.float32), x
            )
            return y * 2

        transfers = ir.host_transfers(jax.make_jaxpr(f)(_f32(4)))
        assert transfers and transfers[0]["prim"] == "pure_callback"

    def test_clean_program(self):
        assert ir.host_transfers(jax.make_jaxpr(lambda x: x * 2)(_f32(4))) == []


# ------------------------------------------------------------------- rules
@smoke
class TestRules:
    def test_precision_finding_fires_below_threshold(self):
        def f(v, x):
            return x @ v  # f32 matmul under a declared bf16 policy

        spec = _spec(f, (_f32(8, 8), _f32(4, 8)), policy="bf16")
        info = ProgramInfo(spec)
        findings = check_precision(info)
        assert [f.rule for f in findings] == ["f32-matmul-under-bf16-policy"]
        assert info.report["matmul"]["coverage"] == 0.0

    def test_precision_silent_for_fp32_policy(self):
        def f(v, x):
            return x @ v

        info = ProgramInfo(_spec(f, (_f32(8, 8), _f32(4, 8)), policy="fp32"))
        assert check_precision(info) == []
        assert info.report["matmul"]["coverage"] is None

    def test_precision_clean_bf16(self):
        def f(v, x):
            return x.astype(jnp.bfloat16) @ v

        info = ProgramInfo(
            _spec(f, (_bf16(8, 8), _f32(4, 8)), policy="bf16")
        )
        assert check_precision(info) == []
        assert info.report["matmul"]["coverage"] == 1.0

    def test_padding_waste_flags_sparse_ladder(self):
        def f(v, x):
            return x @ v

        spec = _spec(
            f, (_f32(8, 8), _f32(8, 8)), kind="serve", bucket=8,
            ladder=(1, 8),
        )
        info = ProgramInfo(spec)
        findings = check_padding(info)
        assert [f.rule for f in findings] == ["padding-waste"]
        assert info.report["padding"]["waste_frac_worst"] == 0.75

    def test_padding_clean_pow2_ladder(self):
        def f(v, x):
            return x @ v

        info = ProgramInfo(
            _spec(
                f, (_f32(8, 8), _f32(4, 8)), kind="serve", bucket=4,
                ladder=(1, 2, 4),
            )
        )
        assert check_padding(info) == []
        assert info.report["padding"]["waste_frac_worst"] == 0.25

    def test_replication_flags_bare_jit_under_mesh(self):
        mesh = jax.sharding.Mesh(
            np.array(jax.devices()[:8]).reshape(8), ("data",)
        )

        def f(w, x):
            return (x @ w).sum()

        args = (_f32(4, 4), _f32(8, 4))
        spec = _spec(
            f,
            args,
            jitted=jax.jit(
                f,
                in_shardings=(
                    NamedSharding(mesh, P()),
                    NamedSharding(mesh, P()),
                ),
            ),
            mesh_size=8,
            data_argnums=(1,),
        )
        findings = check_replication(ProgramInfo(spec))
        assert [f.rule for f in findings] == ["replication-audit"]

    def test_replication_skipped_single_device(self):
        def f(w, x):
            return (x @ w).sum()

        spec = _spec(
            f, (_f32(4, 4), _f32(8, 4)), mesh_size=1, data_argnums=(1,)
        )
        assert check_replication(ProgramInfo(spec)) == []

    def test_donation_unaliased_finding(self):
        def g(s, x):
            return x * 2.0  # s's scalar matches no output -> unusable

        spec = _spec(
            g,
            (_f32(), _f32(4, 4)),
            donate=(0,),
            jitted=jax.jit(g, donate_argnums=(0,), keep_unused=True),
        )
        findings = check_donation(ProgramInfo(spec))
        assert [f.rule for f in findings] == ["donation-alias-audit"]

    def test_no_declared_donation_is_not_audited(self):
        def f(s, x):
            return s + x.sum()

        info = ProgramInfo(_spec(f, (_f32(), _f32(4,)), donate=()))
        assert check_donation(info) == []
        assert "donation" not in info.report


# ------------------------------------------------------------ suppressions
@smoke
class TestSuppressions:
    def _write(self, tmp_path, body):
        f = tmp_path / "site.py"
        f.write_text(body)
        return "site.py"

    def _finding(self, line, rule="padding-waste"):
        from tools.jaxlint.engine import Finding

        return Finding(
            file="site.py", line=line, col=0, rule=rule,
            message="[test/prog] msg", text="def jit_thing():",
        )

    def test_rationale_suppression_silences(self, tmp_path):
        rel = self._write(
            tmp_path,
            "# irlint: disable=padding-waste -- deliberate single bucket\n"
            "def jit_thing():\n    pass\n",
        )
        out = apply_site_suppressions(
            [self._finding(2)], [rel], root=str(tmp_path), full_catalog=True
        )
        assert out == []

    def test_rationale_required(self, tmp_path):
        rel = self._write(
            tmp_path,
            "# irlint: disable=padding-waste\n"
            "def jit_thing():\n    pass\n",
        )
        out = apply_site_suppressions(
            [self._finding(2)], [rel], root=str(tmp_path), full_catalog=True
        )
        rules = sorted(f.rule for f in out)
        assert rules == ["padding-waste", "suppression-missing-rationale"]

    def test_wrong_tag_does_not_silence(self, tmp_path):
        rel = self._write(
            tmp_path,
            "# jaxlint: disable=padding-waste -- wrong analyzer's tag\n"
            "def jit_thing():\n    pass\n",
        )
        out = apply_site_suppressions(
            [self._finding(2)], [rel], root=str(tmp_path), full_catalog=True
        )
        assert [f.rule for f in out] == ["padding-waste"]

    def test_unused_suppression_reported(self, tmp_path):
        rel = self._write(
            tmp_path,
            "# irlint: disable=padding-waste -- nothing here anymore\n"
            "def jit_thing():\n    pass\n",
        )
        out = apply_site_suppressions(
            [], [rel], root=str(tmp_path), full_catalog=True
        )
        assert [f.rule for f in out] == ["unused-suppression"]

    def test_unused_not_reported_under_select(self, tmp_path):
        rel = self._write(
            tmp_path,
            "# irlint: disable=padding-waste -- subset run\n"
            "def jit_thing():\n    pass\n",
        )
        out = apply_site_suppressions(
            [], [rel], root=str(tmp_path), full_catalog=False
        )
        assert out == []


# ---------------------------------------------------------------- frontend
@smoke
class TestFrontend:
    def test_update_baseline_refused_while_empty(self):
        rc = irlint_main(["--update-baseline"])
        assert rc == 2
        with open(
            os.path.join(os.path.dirname(__file__), "..", "tools",
                         "irlint_baseline.json")
        ) as f:
            assert json.load(f)["accepted"] == {}

    def test_unknown_program_glob_exits_2(self):
        assert irlint_main(["definitely/not/a/program"]) == 2

    def test_unknown_rule_select_errors(self):
        with pytest.raises(SystemExit):
            irlint_main(["--select", "no-such-rule"])

    def test_list_rules(self, capsys):
        assert irlint_main(["--list-rules"]) == 0
        out = capsys.readouterr().out
        for name in RULES_BY_NAME:
            assert name in out


# -------------------------------------------------- manifest + acceptance
class TestManifest:
    def test_variant_structs_mirror_weight_transforms(self):
        vs = {"params": {"dense": {"kernel": _f32(8, 16), "bias": _f32(16)}}}
        bf = variant_structs(vs, "bf16")
        assert bf["params"]["dense"]["kernel"].dtype == jnp.bfloat16
        i8 = variant_structs(vs, "int8")
        packed = i8["params"]["dense"]["kernel"]
        assert packed["__int8__"].dtype == jnp.int8
        assert packed["scale"].shape == (16,)  # per-out-channel
        # 1-D leaves stay fp32 (tiny, precision-critical).
        assert i8["params"]["dense"]["bias"].dtype == jnp.float32

    def test_stream_program_clean_and_transfer_free(self):
        infos = lint_programs([stream_program(window=256, n_windows=7,
                                              record_len=1024)])
        assert infos[0].findings == []
        assert infos[0].report["host_transfers"] == []

    def test_train_programs_keep_donation_under_the_cache(self):
        # The suite runs with the persistent compile cache enabled on the
        # CPU backend; donation is declared all the same, and every
        # donated leaf must be accounted as aliased, deferred (mesh
        # lowering) or pruned — none silently lost.
        assert jax.config.jax_compilation_cache_dir
        specs = train_programs(
            "phasenet", compute_dtype=None, window=128, include=("step",)
        )
        spec = specs[0]
        assert spec.donate == (0,)
        info_list = lint_programs(specs, [RULES_BY_NAME["donation-alias-audit"]])
        assert info_list[0].findings == []
        audit = info_list[0].report["donation"]
        assert audit["donated_leaves"] > 0
        accounted = (
            audit["aliased_leaves"]
            + audit["deferred_leaves"]
            + audit["pruned_leaves"]
        )
        assert accounted == audit["donated_leaves"]

    def test_default_manifest_keys_cover_every_boundary(self):
        # Key-level check (no lowering): the manifest names every shipped
        # jit boundary family.
        keys = []
        manifest = default_manifest(match=lambda k: False)
        assert manifest == []  # section pruning works
        # Candidate keys are deterministic; collect via a recording match.
        default_manifest(match=lambda k: keys.append(k) or False)
        blob = "\n".join(keys)
        for needle in (
            "train/jit_step/",
            "train/jit_multi_step/",
            "train/jit_device_aug_step/",
            "train/jit_cached_call/",
            "serve/phasenet/full/",
            "serve/seist_s/trunk/",
            "serve/seist_s/head:",
            "stream/annotate/",
        ):
            assert needle in blob, f"manifest lost the {needle} boundary"


class TestAcceptance:
    def test_full_manifest_green_on_empty_baseline(self, tmp_path):
        """THE gate: every program in the default manifest lowers and
        lints with zero findings against the empty baseline, and the
        report carries the campaign numbers."""
        report = tmp_path / "irlint_report.json"
        rc = irlint_main(["--report", str(report)])
        assert rc == 0
        payload = json.loads(report.read_text())
        summary = payload["summary"]
        assert summary["programs"] >= 12
        assert summary["bf16_coverage_min"] >= 0.9
        assert summary["host_transfers_total"] == 0
        assert summary["padding_waste_worst"] <= 0.5
        # Per-program sections the trend consumers key on.
        some = payload["programs"]["train/jit_step/seist_s_dpk/bf16"]
        assert some["matmul"]["coverage"] >= 0.9
        assert "donation" in some and "sharding" in some

    def test_seist_l_bf16_train_step_coverage(self):
        """The precision-campaign headline number: the seist_l bf16 train
        step runs >= 90% of its matmul FLOPs in bf16."""
        specs = train_programs(
            "seist_l_dpk", compute_dtype="bf16", window=256,
            include=("step",),
        )
        infos = lint_programs(
            specs, [RULES_BY_NAME["f32-matmul-under-bf16-policy"]]
        )
        assert infos[0].findings == []
        cov = infos[0].report["matmul"]["coverage"]
        assert cov is not None and cov >= 0.9

    def test_policy_reaches_all_five_head_families(self):
        """Satellite: the bf16 policy must reach HEAD matmuls for every
        task family, not just the shared trunk — pinned per family via
        the head-program coverage fraction."""
        specs = group_programs(
            "seist_s",
            ("dpk", "pmp", "emg", "baz", "dis"),
            buckets=(4,),
            variants=("bf16",),
            window=256,
        )
        heads = [s for s in specs if "/head:" in s.key]
        assert len(heads) == 5
        infos = lint_programs(
            heads, [RULES_BY_NAME["f32-matmul-under-bf16-policy"]]
        )
        for info in infos:
            assert info.findings == [], info.spec.key
            cov = info.report["matmul"]["coverage"]
            assert cov is not None and cov >= 0.9, (
                f"{info.spec.key}: head matmuls not reached by the bf16 "
                f"policy (coverage {cov})"
            )
        # ... and the trunk too, for completeness.
        trunk = [s for s in specs if "/trunk/" in s.key]
        tinfo = lint_programs(
            trunk, [RULES_BY_NAME["f32-matmul-under-bf16-policy"]]
        )[0]
        assert tinfo.report["matmul"]["coverage"] >= 0.9
