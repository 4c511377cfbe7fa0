#!/usr/bin/env python3
"""Build a checkout in which the program's own spans and scopes reach the
result line, for measuring until a ``benchmark`` PR wires them in.

    python3 benchmark/tools/program_overlay.py <checkout> <destination>

A PR that is not a ``benchmark`` PR may add files to the benchmark but edit
none, and the second reduction (``trace/program.py``) needs one edit to a
file that is there: ``trace/capture.py reduce_and_remove`` has the only
hands on the ``.xplane.pb`` before it is deleted. This tool makes that edit
in a COPY: it copies ``<checkout>`` (this tree, or an unpacked parent) to
``<destination>``, lays this tree's ``benchmark/`` over it as the driver
does, adds to the copy's ``capture.py`` the call that puts the reduction
under the key ``program`` (and keeps it whole, spans and tables, in
``.bench_trace/<cell>.program.json`` of the copy), and appends the entries of
``tools/program_metrics.json`` to the copy's ``BENCHMARK.json``. Run the
benchmark's command from ``<destination>``. A parent that has no spans
leaves the new metrics out; it does not fail.
"""
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
OLD = "        return trace_reduce.reduce(trace_reduce.load_xplane(found[0]))\n"
NEW = ("        out = trace_reduce.reduce(trace_reduce.load_xplane(found[0]))\n"
       "        from benchmark.trace import program as trace_program\n"
       "        out[\"program\"] = trace_program.reduce(\n"
       "            trace_program.load(found[0]))\n"
       "        import json\n"
       "        with open(directory.rstrip(\"/\") + \".program.json\",\n"
       "                  \"w\") as fh:  # kept beside the deleted trace\n"
       "            json.dump(out[\"program\"], fh)\n"
       "        return out\n")
SKIP = shutil.ignore_patterns(".git", "chiprun_out", "_bench_archive",
                              "_archive", ".jax_cache", ".bench_trace",
                              "__pycache__", ".pytest_cache")


def main() -> int:
    src, dest = (os.path.abspath(p) for p in sys.argv[1:3])
    shutil.rmtree(dest, ignore_errors=True)
    shutil.copytree(src, dest, ignore=SKIP)
    shutil.copytree(os.path.join(HERE, "benchmark"),
                    os.path.join(dest, "benchmark"), ignore=SKIP,
                    dirs_exist_ok=True)
    capture = os.path.join(dest, "benchmark", "trace", "capture.py")
    with open(capture) as fh:
        text = fh.read()
    if text.count(OLD) != 1:
        raise SystemExit(f"{capture}: the line this tool edits is not there")
    with open(capture, "w") as fh:
        fh.write(text.replace(OLD, NEW))
    with open(os.path.join(HERE, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    with open(os.path.join(HERE, "benchmark", "tools",
                           "program_metrics.json")) as fh:
        bench["per_layer"] += json.load(fh)["per_layer"]
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh, indent=1)
    print(f"overlay of {src} at {dest}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
