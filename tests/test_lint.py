"""Standing-policy lint gate (``repro.analysis.lint``): the repo must
be clean, and each rule must actually fire on a violating snippet."""

import subprocess
import sys
import textwrap

from repro.analysis import lint


def test_repo_is_lint_clean():
    findings = lint.lint_repo()
    assert not findings, "\n".join(str(f) for f in findings)


def test_cli_exits_zero_on_clean_repo():
    proc = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint"],
        capture_output=True, text=True, cwd=str(lint.repo_root()))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "lint: clean" in proc.stdout


def _lint_snippet(tmp_path, code, name="snippet.py"):
    f = tmp_path / name
    f.write_text(textwrap.dedent(code))
    return lint.lint_file(f)


def test_L001_flags_direct_jax_shard_map(tmp_path):
    rules = {f.rule for f in _lint_snippet(tmp_path, """
        from jax.experimental.shard_map import shard_map
        from jax import check_vma
        import jax

        def f():
            return jax.shard_map
        """)}
    assert rules == {"L001"}


def test_L001_allows_the_compat_shim(tmp_path):
    shim = tmp_path / "parallel"
    shim.mkdir()
    (shim / "compat.py").write_text(
        "from jax.experimental.shard_map import shard_map\n")
    assert not lint.lint_paths([shim])


def test_L002_flags_direct_hypothesis_import(tmp_path):
    rules = {f.rule for f in _lint_snippet(tmp_path, """
        import hypothesis
        from hypothesis import given
        """)}
    assert rules == {"L002"}
    # the compat shim itself is exempt
    assert not _lint_snippet(tmp_path, "import hypothesis\n",
                             name="_hypothesis_compat.py")


def test_L003_flags_interpret_true_default_outside_kernels(tmp_path):
    findings = _lint_snippet(tmp_path, """
        def run(x, interpret=True):
            return x

        def keyword_only(x, *, interpret=True):
            return x

        def threaded(x, interpret):
            return x

        def explicit_false(x, interpret=False):
            return x
        """)
    assert [f.rule for f in findings] == ["L003", "L003"]


_CLOCKY = """
    import time

    def pump():
        t0 = time.monotonic()
        time.sleep(0.01)
        return time.perf_counter() - t0
    """


def test_L005_flags_bare_clock_calls_in_serve(tmp_path):
    d = tmp_path / "serve"
    d.mkdir()
    (d / "loopy.py").write_text(textwrap.dedent(_CLOCKY))
    rules = [f.rule for f in lint.lint_file(d / "loopy.py")]
    assert rules == ["L005", "L005", "L005"]


def test_L005_allows_clock_parameter_defaults(tmp_path):
    d = tmp_path / "serve"
    d.mkdir()
    (d / "injected.py").write_text(textwrap.dedent("""
        import time

        def run(clock=time.monotonic, *, sleep=time.sleep):
            sleep(0.0)
            return clock()
        """))
    assert not lint.lint_file(d / "injected.py")


def test_L005_is_scoped_to_serve_paths(tmp_path):
    # the same violating code outside serve/ is fine —
    # benchmarks and tests time things with wall clocks on purpose
    assert not _lint_snippet(tmp_path, _CLOCKY)


def test_L006_flags_bare_clock_calls_inside_obs(tmp_path):
    d = tmp_path / "obs"
    d.mkdir()
    (d / "tracey.py").write_text(textwrap.dedent(_CLOCKY))
    rules = [f.rule for f in lint.lint_file(d / "tracey.py")]
    # obs/ is outside L005's serve/ scope, so each bare clock
    # call is exactly one L006 finding
    assert rules == ["L006", "L006", "L006"]


def test_L006_allows_clock_defaults_and_injected_clocks_in_obs(tmp_path):
    d = tmp_path / "obs"
    d.mkdir()
    (d / "tracer.py").write_text(textwrap.dedent("""
        import time

        class Tracer:
            def __init__(self, clock=time.perf_counter):
                self._clock = clock

            def now(self):
                return self._clock()
        """))
    assert not lint.lint_file(d / "tracer.py")


def test_L006_flags_set_active_mutation_outside_obs(tmp_path):
    rules = {f.rule for f in _lint_snippet(tmp_path, """
        from repro.obs.tracer import set_active

        def hijack(tracer):
            set_active(tracer)
        """)}
    assert rules == {"L006"}
    rules = {f.rule for f in _lint_snippet(tmp_path, """
        from repro.obs import tracer as trc

        def hijack(t):
            trc.set_active(t)
        """, name="other.py")}
    assert rules == {"L006"}


def test_L006_allows_set_active_inside_obs_and_activate_scopes(tmp_path):
    d = tmp_path / "obs"
    d.mkdir()
    (d / "tracer.py").write_text(textwrap.dedent("""
        def set_active(tracer):
            return tracer

        class _Activation:
            def __enter__(self):
                return set_active(self)
        """))
    assert not lint.lint_file(d / "tracer.py")
    # the sanctioned caller idiom — a scoped activate() — is clean
    assert not _lint_snippet(tmp_path, """
        def run(tracer):
            with tracer.activate():
                pass
        """)


def test_L007_flags_raw_backend_kwargs_at_call_sites(tmp_path):
    findings = _lint_snippet(tmp_path, """
        def run(conv, srv):
            a = conv(x, w, interpret=True)
            b = srv.pipeline(2, use_kernel=False)
            # positional args and other kwargs are fine
            c = conv(x, w, target="compiled")
            return a, b, c
        """)
    assert [f.rule for f in findings] == ["L007", "L007"]


def test_L007_exempts_kernels_tree_and_from_flags(tmp_path):
    d = tmp_path / "kernels"
    d.mkdir()
    (d / "wrapper.py").write_text(textwrap.dedent("""
        def call(x):
            return pallas_call(x, interpret=True)
        """))
    assert not lint.lint_file(d / "wrapper.py")
    # the sanctioned legacy-boolean adapter is exempt by callee name
    assert not _lint_snippet(tmp_path, """
        from repro.core.exec_target import from_flags

        def adapt(flag):
            return from_flags(use_kernel=flag, compute=True)
        """)


def test_L008_flags_lax_conv_in_backward_paths(tmp_path):
    findings = _lint_snippet(tmp_path, """
        import jax

        def _bwd(res, g):
            y = jax.lax.conv_general_dilated(res, g, (1, 1), "SAME")

            def inner():                  # closure is still backward
                return jax.lax.conv(res, g, (1, 1), "SAME")

            return y, inner()

        def wgrad_helper(x, w):
            return jax.lax.conv(x, w, (1, 1), "SAME")
        """)
    assert [f.rule for f in findings] == ["L008", "L008", "L008"]


def test_L008_exempts_lax_fallbacks_and_forward_paths(tmp_path):
    assert not _lint_snippet(tmp_path, """
        import jax

        def _dgrad_lax_fallback(x, w, gy):
            return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME")

        def _bwd(res, g):
            def esc_lax_fallback():       # enclosing suffix sanctions
                return jax.lax.conv(res, g, (1, 1), "SAME")

            return esc_lax_fallback()

        def forward(x, w):                # not a backward path at all
            return jax.lax.conv(x, w, (1, 1), "SAME")
        """)


def test_syntax_errors_are_findings_not_crashes(tmp_path):
    findings = _lint_snippet(tmp_path, "def broken(:\n")
    assert findings and findings[0].rule == "parse"
