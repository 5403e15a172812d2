"""What the serving tests share: a preemption forced through the engine's
own `_make_room`.

Admission reserves every running row's growth to its known end (ISSUE 55),
so a pool that is merely small no longer dries under rows that hold tokens:
a waiter stands in the queue instead. `_preempt` and the resume path stay
as the net under copy-on-write, the sliding layers' pool and speculation's
lookahead, and the tests of that path reach it by asking the engine for
room it does not have, as a dry pool did."""
import contextlib


def preempt_youngest(eng) -> bool:
    """Make room once more than the pool asks for: the first call settles a
    pending step, the next preempts the youngest running row. False where
    fewer than two rows run (the engine would have nobody to preempt)."""
    before = eng.stats["preemptions"]
    while eng.stats["preemptions"] == before:
        if len(eng._running) < 2:
            return False
        eng._make_room(eng._running[0])
    return True


@contextlib.contextmanager
def preempting(eng, times: int = 2, every: int = 3):
    """Inside the block `eng.step()` (and so `run_until_drained`) first
    preempts the youngest running row, `times` times in all, at steps at
    least `every` apart on which two rows or more run: rows come back with
    tokens of their own to prefill again, as under the dry pool these tests
    used to build."""
    step = eng.step
    left, since = times, every

    def stepped():
        nonlocal left, since
        since += 1
        if left and since >= every and preempt_youngest(eng):
            left, since = left - 1, 0
        return step()

    eng.step = stepped
    try:
        yield
    finally:
        del eng.step
