"""Set a configuration's ``LIMITS`` from what ``tools/readings.py``
printed, by the rule every serving cell's limits follow: each limit is
the geometric middle between the largest sound reading and the smallest
reading of the control, rounded to two figures.

    python benchmark/tools/limits_from_readings.py readings.out \
        benchmark/reference/<config>.py [--write]

Prints the readings, each limit and its factor from either side; with
``--write`` rewrites the numbers in the reference file's ``LIMITS``.  A
limit whose two readings do not lie a factor 2 apart is refused (exit 1):
no limit lies between them with room.
"""
from __future__ import annotations

import re
import sys


def main(argv) -> int:
    out, ref = argv[0], argv[1]
    seen: dict = {}
    with open(out) as f:
        for m in re.finditer(r"^\[reading\] seed=\d+ (sound|control) "
                             r"(\w+)=([-\w.+]+) ", f.read(), re.M):
            seen.setdefault((m[2], m[1]), []).append(float(m[3]))
    with open(ref) as f:
        text = f.read()
    ok = True
    for name in sorted({n for n, _ in seen}):
        sound, control = seen.get((name, "sound"), []), \
            seen.get((name, "control"), [])
        if len(sound) < 4 or len(control) < 2:
            print(f"{name}: {len(sound)} sound and {len(control)} control "
                  "readings; four and two are needed")
            ok = False
            continue
        lo, hi = max(sound), min(control)
        if lo <= 0:
            # a mean over a few tokens that all agree: the mean over all
            # of them is the same quantity, read on more tokens
            lo = max(seen.get(("logit_gap_mean", "sound"), [0.0]))
            print(f"{name}: every sound reading 0; the largest sound "
                  f"logit_gap_mean, {lo:.4g}, stands in")
        if lo <= 0 or hi < 2 * 2 * lo:
            print(f"{name}: sound {lo:.4g}, control {hi:.4g}: no limit "
                  "lies between them with room")
            ok = False
            continue
        limit = float(f"{(lo * hi) ** 0.5:.2g}")
        print(f"{name}: sound at most {lo:.4g} over {len(sound)} seeds "
              f"{[float(f'{v:.4g}') for v in sound]}, control at least "
              f"{hi:.4g} over {len(control)} "
              f"{[float(f'{v:.4g}') for v in control]}; limit {limit:g}, "
              f"a factor {limit / lo:.2g} above and {hi / limit:.2g} below")
        text, n = re.subn(rf'("{name}": )[\d.e-]+,', rf"\g<1>{limit:g},",
                          text)
        ok = ok and n == 1
    if ok and "--write" in argv:
        with open(ref, "w") as f:
            f.write(text)
        print(f"wrote {ref}")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
