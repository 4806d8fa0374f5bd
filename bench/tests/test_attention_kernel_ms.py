"""attention_kernel_ms reads the fused attention's kernels by name."""

from types import SimpleNamespace

from spec import metric_reader


def test_sums_the_splash_kernels_per_step():
    ops = [("splash_mha_fwd_residuals.31 (f32[512,128], bf16[16,4096,64])", 0.5),
           ("splash_mha_dkv_no_residuals.11 (bf16[16,4096,64])", 1.0),
           ("splash_mha_dq_no_residuals.11 bf16[16,4096,64]", 0.25),
           ("fusion.984 (f32[8,2,4096], bf16[8,2,4096,4096])", 3.0),
           ("gmm.3 bf16[32768,512]", 2.0)]
    ctx = {"trace": SimpleNamespace(top_ops=ops), "steps": 250}
    assert metric_reader("attention_kernel_ms")(ctx) == 1e3 * 1.75 / 250


def test_nothing_where_attention_is_unfused():
    ops = [("fusion.984 (f32[8,2,4096], bf16[8,2,4096,4096])", 3.0)]
    ctx = {"trace": SimpleNamespace(top_ops=ops), "steps": 250}
    assert metric_reader("attention_kernel_ms")(ctx) is None
