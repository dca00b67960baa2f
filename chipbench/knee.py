"""Find a serving cell's knee, once, on the chip, for any serving driver:
``chipbench/sweep.py``'s own loop and printer, with the engine built by
the driver that the cell's ``driver`` names (``sweep.py`` builds
``drivers/serve.py``'s whatever the cell says, and a PR that is no
``benchmark`` PR may not edit it: fold this file into it then).

    python3 chipbench/knee.py --workload <cell> --seed <n> --seconds <s> --rates 1,2,3

Arguments and output are ``sweep.py``'s: one JSON line a rate.
"""

from __future__ import annotations

import importlib
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    from chipbench import sweep
    from chipbench.drivers import serve

    def build_engine(ctx):
        driver = importlib.import_module(
            f"chipbench.drivers.{ctx.cell['driver']}")
        return driver.build_engine(ctx)

    theirs = serve.build_engine   # the one name sweep.main looks up
    serve.build_engine = build_engine
    try:
        return sweep.main(argv)
    finally:
        serve.build_engine = theirs


if __name__ == "__main__":
    sys.exit(main())
