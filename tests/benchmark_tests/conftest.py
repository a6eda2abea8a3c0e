"""The program records its spans only while a profiler session is active
(``chainermn_tpu.tracing``). ``test_drivers.py``'s runs with the recorded
fixture as their trace keep the profiler off, so the readers of the
program's spans would find no iteration there: those tests run under a
session of their own, which is the program's one switch."""
import pytest


@pytest.fixture(autouse=True)
def profiler_session_where_the_fixture_is_the_trace(request, tmp_path):
    if "fixture_for_trace" not in request.fixturenames:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1      # annotations only: a small trace
    jax.profiler.start_trace(str(tmp_path / "session"), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
