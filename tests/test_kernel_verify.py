"""Static Pallas kernel verifier (`repro.analysis.kernel_model` +
`kernel_verify`): the shipped kernels must verify clean at every config
shape, and a mutation-tested negative suite proves each rule actually
fires — every programmatically injected bug class must be caught by the
*matching* rule (a verifier that passes everything proves nothing)."""
import ast

import pytest

from repro.analysis import kernel_model as km
from repro.analysis import kernel_verify as kv


@pytest.fixture(scope="module")
def models():
    return {m.name: m for m in km.lint_models()}


def rules(findings):
    return {f.rule for f in findings}


# ----------------------------------------------------- model extraction ----

def test_extracts_all_kernels(models):
    assert set(models) == {"bgmv_shrink", "bgmv_expand", "mbgmv_shrink",
                           "mbgmv_expand", "flash_attention",
                           "paged_attention"}
    for m in models.values():
        assert m.grid, m.name
        assert m.out_specs, m.name
        assert m.kernel_ast is not None, m.name
        assert m.path.endswith(".py"), m.name


def test_param_roles_line_up(models):
    roles = models["paged_attention"].param_roles()
    assert roles["bt_ref"] == "scalar"
    assert roles["q_ref"] == "input"
    assert roles["o_ref"] == "output"
    assert roles["acc_ref"] == "scratch"


def test_index_map_evaluates_with_scalars(models):
    m = models["paged_attention"]
    # the K-page spec gathers through the prefetched block table
    kspec = m.in_specs[1]
    c = m.eval_index(kspec, (0,) * len(m.grid))
    assert all(isinstance(x, int) for x in c)


def test_vmem_footprint_counts_double_buffering(models):
    m = models["bgmv_shrink"]
    fp = m.vmem_footprint()
    assert fp["total_bytes"] == \
        2 * (fp["in_bytes"] + fp["out_bytes"]) + fp["scratch_bytes"]
    assert fp["total_bytes"] > 0


def test_clamped_scalar_detected_through_closure(models):
    m = models["paged_attention"]
    # page = lambda ...: jnp.maximum(bt[b, j], 0) is a closure the K/V and
    # pos-page index maps call — the clamp must be traced through it
    assert kv.clamped_scalar_operands(m, m.in_specs[1]) == {0}
    assert kv.clamped_scalar_operands(m, m.in_specs[0]) == set()


def test_mamba_has_no_attention_models():
    case = km.case_from_config(__import__(
        "repro.configs.base", fromlist=["get_config"]
    ).get_config("mamba2-130m"))
    names = {m.name for m in km.build_models(case)}
    assert "flash_attention" not in names
    assert "paged_attention" not in names


# ------------------------------------------------------------ clean runs ----

def test_shipped_kernels_verify_clean(models):
    findings = kv.verify_models(list(models.values()))
    assert findings == [], [f.render() for f in findings]


def test_all_configs_verify_clean_and_within_budget():
    for label, case_models in km.config_models():
        findings = kv.verify_models(case_models)
        assert findings == [], (label, [f.render() for f in findings])
        for m in case_models:
            fp = m.vmem_footprint()
            assert fp["total_bytes"] <= kv.VMEM_BUDGET_BYTES, \
                (label, m.name, fp)


# -------------------------------------------------- mutation suite (>=6) ----

def test_mutation_oob_index_map_caught(models):
    # off-by-one page gather: the clamped block-table index map shifted by
    # +1 block walks past the page pool
    mutant = kv.shift_index_map(models["paged_attention"], 1, 0)
    assert rules(kv.verify_model(mutant)) == {"kernel-bounds"}


def test_mutation_negative_index_map_caught(models):
    mutant = kv.shift_index_map(models["bgmv_shrink"], 1, 0, delta=-1)
    assert "kernel-bounds" in rules(kv.verify_model(mutant))


def test_mutation_noncontiguous_revisit_caught(models):
    # reversing the grid makes output revisits strided: the classic TPU
    # revisit race that interpret mode cannot see
    mutant = kv.swap_grid_order(models["flash_attention"])
    assert "kernel-race" in rules(kv.verify_model(mutant))


def test_mutation_missing_scratch_init_caught(models):
    mutant = kv.drop_when_block(models["paged_attention"], "init")
    found = kv.verify_model(mutant)
    assert rules(found) == {"kernel-scratch"}
    assert any("initialization" in f.message for f in found)


def test_mutation_missing_flush_caught(models):
    mutant = kv.drop_when_block(models["flash_attention"], "flush")
    found = kv.verify_model(mutant)
    assert rules(found) == {"kernel-scratch"}
    assert any("flush" in f.message for f in found)


def test_mutation_clamp_without_guard_caught(models):
    # removing the pl.when(bt >= 0) guard leaves the clamped gather's
    # stale/foreign page contributing to the output — isolation bug
    mutant = kv.drop_when_block(models["paged_attention"], "data")
    found = kv.verify_model(mutant)
    assert "kernel-bounds" in rules(found)
    assert any("clamps scalar operand" in f.message for f in found)


def test_mutation_missing_preferred_element_type_caught(models):
    for name in ("mbgmv_expand", "flash_attention", "paged_attention"):
        mutant = kv.strip_preferred_element_type(models[name])
        found = kv.verify_model(mutant)
        assert "kernel-dtype" in rules(found), name
        assert any("preferred_element_type" in f.message
                   for f in found), name


def test_mutation_broken_carry_caught(models):
    mutant = kv.break_carry(models["flash_attention"], "acc_ref")
    found = kv.verify_model(mutant)
    assert "kernel-scratch" in rules(found)
    assert any("carry" in f.message for f in found)


def test_mutation_vmem_budget_violation_caught(models):
    m = models["flash_attention"]
    fp = m.vmem_footprint()
    found = kv.verify_model(m, vmem_budget=fp["total_bytes"] - 1)
    assert rules(found) == {"kernel-vmem"}


def test_drop_when_block_requires_a_match(models):
    # bgmv_expand has no flush-guarded block: the mutation helper must
    # refuse rather than silently produce an unmutated "mutant"
    with pytest.raises(ValueError):
        kv.drop_when_block(models["bgmv_expand"], "flush")


# ------------------------------------------------- guard classification ----

def test_guard_classification(models):
    body = kv.KernelBody(models["paged_attention"])
    kinds = []
    for pred in body.guard_preds:
        kinds.append(body.classify_guard(pred)[0])
    assert "init" in kinds and "flush" in kinds and "data" in kinds


def test_mutated_ast_is_still_parseable(models):
    mutant = kv.drop_when_block(models["paged_attention"], "init")
    # the transform must leave a structurally valid function AST behind
    assert isinstance(mutant.kernel_ast, ast.FunctionDef)
    compile(ast.Module(body=[mutant.kernel_ast], type_ignores=[]),
            "<mutant>", "exec")
    # and must not have touched the original model
    body = kv.KernelBody(models["paged_attention"])
    assert any(body.classify_guard(p)[0] == "init"
               for p in body.guard_preds)
