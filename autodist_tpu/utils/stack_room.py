"""Room on the interpreter's frame stack for a deep, hot call.

CPython (3.11 on) keeps a thread's Python frames on a "data stack" of
16 KiB chunks.  A frame that does not fit in the current chunk takes a
new chunk from the OS (``mmap``), and the chunk goes back (``munmap``)
the moment that frame returns.  A loop whose calls happen to straddle a
chunk's end therefore pays both system calls *per call*, for as long as
it runs.  jax's lowering of a large program is such a loop
(``mlir.jaxpr_subcomp`` makes a handful of Python calls for each of the
program's thousands of equations), and whether it straddles depends on
nothing but how many frames, of what sizes, happen to lie below it: the
script that started the process, the callers, a local variable more in
any of them.  On a v5e host under gVisor, where a system call is slow,
lowering the serving cell's decode program took 22.5 s under
``python benchmark/run.py`` and 0.6 s when the very same file was
started through ``runpy`` (four frames more below it): 25 s of the
serving cell's 52 s of warm set-up (PERF.md section 6, PR 24).

:func:`call_with_stack_room` takes the chance out: it calls through a
function whose own frame is so large that the interpreter gives it a
chunk of 512 KiB, more than half of it free, and everything the call
does runs inside that one chunk, wherever the caller stood.
"""
from __future__ import annotations

# Local-variable slots of the trampoline's frame: 33,003 x 8 bytes is
# 258 KiB, which the interpreter rounds up to a 512 KiB chunk and leaves
# ~250 KiB of it free: some 600 frames of ordinary size, where jax's
# lowering of a nested program goes ~150 deep.
_SLOTS = 33_000
_trampoline = None


def _build_trampoline():
    # The names after ``return`` are never assigned (the compiler drops
    # the dead statement) but each still owns a slot of the frame.
    names = " = ".join(f"_{i}" for i in range(_SLOTS))
    src = ("def _roomy(fn, args, kwargs):\n"
           "    return fn(*args, **kwargs)\n"
           f"    {names} = None\n")
    scope: dict = {}
    exec(compile(src, "<stack_room>", "exec"), scope)
    return scope["_roomy"]


def call_with_stack_room(fn, *args, **kwargs):
    """``fn(*args, **kwargs)``, run where its frames cannot straddle the
    end of a stack chunk (costs ~0.1 ms: one chunk taken and returned)."""
    global _trampoline
    if _trampoline is None:
        _trampoline = _build_trampoline()
    return _trampoline(fn, args, kwargs)


class FirstCallWithRoom:
    """A jitted function whose first call, the one that traces and
    lowers it, runs under :func:`call_with_stack_room`; later calls go
    straight through.  Everything else (``lower``, ``trace``, ...) is
    the jitted function's own."""

    __slots__ = ("fn", "_first")

    def __init__(self, fn):
        self.fn = fn
        self._first = True

    def __call__(self, *args, **kwargs):
        if self._first:
            self._first = False
            return call_with_stack_room(self.fn, *args, **kwargs)
        return self.fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self.fn, name)
