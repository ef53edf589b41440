"""One process per chip: the device check and the child-process runner
that chip_smoke.py and bench.py share.

A chip belongs to one process at a time. A parent that has touched JAX
holds it, and a child that needs it then fails or hangs — so the parents
here never import JAX: every phase that drives the chip runs in a
spawned child that exits before the next device owner starts.
"""

from __future__ import annotations

import multiprocessing
import traceback


class ChipError(RuntimeError):
    """The chip is missing, or a phase that drove it failed."""


def require_tpu(min_count: int = 1) -> dict:
    """The device as JAX reports it; raises unless it is a TPU with at
    least ``min_count`` devices. Measuring on the CPU in its place would
    be a different result, not a slower one."""
    import jax

    devices = jax.devices()
    info = {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    if info["platform"] != "tpu":
        raise ChipError(f"JAX finds no TPU (platform {info['platform']!r})")
    if info["count"] < min_count:
        raise ChipError(f"need {min_count} TPU devices, JAX finds "
                        f"{info['count']}")
    return info


def _child(conn, fn, args) -> None:
    try:
        conn.send(("ok", fn(lambda msg: conn.send(("msg", msg)), *args)))
    except BaseException:
        conn.send(("error", traceback.format_exc()))
        raise SystemExit(1)
    finally:
        conn.close()


def run_in_child(fn, *args, on_message=None):
    """Run ``fn(send, *args)`` in a spawned process and return its result;
    the child has exited when this returns. Spawned, not forked: the child
    inherits none of this process's state. ``send(msg)`` passes a message
    to ``on_message(msg)`` here while the child keeps working. A child that
    raises or dies raises ChipError here, with its traceback."""
    ctx = multiprocessing.get_context("spawn")
    recv, send = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=_child, args=(send, fn, args),
                       name=f"chip-{getattr(fn, '__name__', 'child')}")
    proc.start()
    send.close()
    done = False
    try:
        while True:
            try:
                kind, value = recv.recv()
            except EOFError:
                kind, value = "error", "child exited without a result\n"
            if kind != "msg":
                break
            if on_message is not None:
                on_message(value)
        done = True
    finally:
        if not done:  # this side failed: do not wait out the child's work
            proc.terminate()
        proc.join()
    if kind == "error":
        raise ChipError(f"{getattr(fn, '__name__', fn)} failed in its child "
                        f"process (exit status {proc.exitcode}):\n{value}")
    if proc.exitcode != 0:
        raise ChipError(f"{fn.__name__} child exited with {proc.exitcode}")
    return value
