"""The serving launcher as a library call, and its compile cache.

``launch.serve.main(argv)`` runs in-process and returns its summary: at
smoke widths a dense compressed model and an MoE model under tiered
residency must serve every request through the fused kernels with no
fallback.  The compile cache lives where ``JAX_COMPILATION_CACHE_DIR``
says, else at one fixed checkout-local path.
"""
import jax
import pytest

from repro.launch import compile_cache, serve

TRACE = ["--batch", "3", "--slots", "2", "--prompt-len", "8",
         "--max-new", "4", "--stagger", "1"]


@pytest.mark.parametrize("argv,want", [
    (["--arch", "internlm2-1.8b", "--mode", "compressed"], {"fused"}),
    (["--arch", "deepseek-v2-lite-16b", "--layers", "2", "--mode",
      "compressed", "--residency", "tiered"], {"fused", "grouped_fused"}),
])
def test_main_serves_through_fused_kernels(argv, want, capsys, monkeypatch,
                                          tmp_path):
    # an env-named cache dir leaves this process's JAX config untouched
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    s = serve.main(argv + TRACE)
    assert s["completed"] == 3 and set(s["reasons"]) == {"max_new"}
    assert set(s["dispatch"]) == want, s["dispatch"]
    assert s["fallbacks"] == {} and s["last_rung"] == "fused"
    assert all(len(toks) == 4 for toks in s["outputs"].values())
    assert s["compressed_mib"] > 0 and s["compile_s"] >= 0
    out = capsys.readouterr().out
    assert f"kept {s['n_layers']} of 3 layers" in out


def test_depth_cut_keeps_leading_dense_layers():
    args = serve._parser().parse_args(
        ["--arch", "deepseek-v2-lite-16b", "--full", "--layers", "2"])
    cfg = serve._serving_config(args)
    assert (cfg.n_layers, cfg.first_dense_layers, cfg.n_experts,
            cfg.d_model) == (2, 1, 64, 2048)
    args.layers = 1          # would drop every MoE layer
    with pytest.raises(SystemExit):
        serve._serving_config(args)


def test_compile_cache_dir_from_env(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.setup() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads env


def test_compile_cache_dir_fixed_without_env(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first, second = compile_cache.cache_dir(), compile_cache.cache_dir()
    assert first == second == str(compile_cache.CHECKOUT / ".jax_cache")
    gitignore = (compile_cache.CHECKOUT / ".gitignore").read_text()
    assert ".jax_cache/" in gitignore.split()
