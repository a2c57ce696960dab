"""The chip benchmark's harness; ``run.py`` beside this package is its
entry point.

A cell whose padded index table (``2**table_bits * padded_cap`` int32)
is larger than every chip in ``peaks.json`` runs only on a program that
places such an index as CSR lines (`repro.core.seedmap.LinedCSRSeedMap`).
A program without them builds the whole index on the host and outgrows
the host's memory before its placement can fail, so it is killed with
neither a result nor an error.  When ``run.py`` imports this package for
such a cell and the program lacks CSR lines, the run ends here, at once,
with exit status 1 and the reason.  Every other cell is left alone.
"""
import json
import sys
from pathlib import Path


def workload_arg(argv) -> str | None:
    """The ``--workload`` value of a ``run.py`` command line."""
    for i, arg in enumerate(argv):
        if arg == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith("--workload="):
            return arg.split("=", 1)[1]
    return None


def refuse_unplaceable_index(root: Path, workload: str) -> None:
    """Exit with status 1 where ``workload``'s padded index table fits no
    chip and the program has no CSR line layout to place it in."""
    from chipbench.cell import load_cell

    cell = load_cell(root, workload)
    sm = cell.config["seedmap"]
    padded = 2 ** sm["table_bits"] * sm["padded_cap"] * 4
    peaks = json.loads((cell.bench_dir / "peaks.json").read_text())
    if padded <= max(p["hbm_bytes"] for p in peaks.values()):
        return
    import repro.core.seedmap as seedmap

    if not hasattr(seedmap, "LinedCSRSeedMap"):
        raise SystemExit(
            f"{workload}: the padded index table ({padded:.3e} bytes) fits "
            "no chip in peaks.json, and this program has no CSR line "
            "layout (repro.core.seedmap.LinedCSRSeedMap) to place the "
            "index in; not run")


_MAIN = getattr(sys.modules.get("__main__"), "__file__", None)
if _MAIN and (Path(_MAIN).resolve()
              == Path(__file__).resolve().parents[1] / "run.py"):
    _WORKLOAD = workload_arg(sys.argv[1:])
    if _WORKLOAD is not None:
        refuse_unplaceable_index(Path.cwd(), _WORKLOAD)
