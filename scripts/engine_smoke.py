"""CI engine smoke: the quickstart + a short map_stream serve.

Runs the two engine entry points end to end (under whatever
``REPRO_BACKEND`` the job sets — CI uses the interpret-mode kernels) and
asserts that neither raised a ``DeprecationWarning``.

  PYTHONPATH=src REPRO_BACKEND=interpret python scripts/engine_smoke.py
"""
import runpy
import sys
import warnings

ARGS = ["serve", "--ref-len", "120000", "--batch", "64",
        "--batches", "3", "--table-bits", "18"]


def main():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        runpy.run_path("examples/quickstart.py", run_name="__main__")
        sys.argv = ARGS
        runpy.run_module("repro.launch.serve", run_name="__main__")
    dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
    assert not dep, [str(w.message) for w in dep]
    print("engine smoke: no deprecation warnings")


if __name__ == "__main__":
    main()
