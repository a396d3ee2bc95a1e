"""The harness's heartbeat measures the event loop's lag and arms nothing."""
import asyncio
import inspect
import json
import types

from yardstick import run


def test_the_heartbeat_collects_lag_and_arms_no_timed_dump():
    # `faulthandler.dump_traceback_later` reads every thread's frames
    # without the interpreter lock: fired under running threads it ends
    # the process with SIGSEGV (exit 139; PR 25, and PR 33's first check).
    # A silent loop is reported by the program's own watchdog instead.
    assert "dump_traceback_later" not in inspect.getsource(run)
    me = types.SimpleNamespace(loop_lag_ms=[])

    async def beat():
        task = asyncio.ensure_future(run.Run._heartbeat(me))
        await asyncio.sleep(0.3)
        task.cancel()

    asyncio.run(beat())
    assert 3 <= len(me.loop_lag_ms) <= 6
    assert all(-1.0 < lag < 200.0 for lag in me.loop_lag_ms)


def test_a_silent_loop_costs_a_run_its_rate_not_its_life(tmp_path, capfd):
    """The event loop held for longer than the program's `STALL_S` in the
    window, worker threads alive: the run ends, is correct, shows the lag,
    and the stall is the program's own watchdog's to report."""
    import contextlib
    import time

    from yardstick.tests import test_run_tiny as tiny

    @contextlib.contextmanager
    def stalled(run):
        asyncio.get_running_loop().call_later(0.5, time.sleep, 1.6)
        yield

    out = tiny._in_process(tiny.make_checkout(tmp_path), "tiny.sumall-steady",
                           53, breakage=stalled)
    assert out["correct"] is True and out["failed"] == 0
    window = [json.loads(line.split("] ", 1)[1])
              for line in capfd.readouterr().out.splitlines()
              if line.startswith(("[window]", "[latency_ms]"))]
    assert window[0]["loop_lag"][-1] >= 1500.0
    assert window[1]["loop_stalls_reported"] >= 1
    assert 0.0 <= window[1]["gc_pause_s_since_open"] < 2.0
