"""Declare per-layer metrics that have a file and no entry in the
``BENCHMARK.json`` of the checkout this runs in: one entry each, built
from the metric's own file (``layer_metrics/<name>.json``) and listing
every cell but those the names' file says cannot report it (``not_in``),
appended at the end of ``per_layer`` in the order of the names' file, as
a later ``benchmark`` PR would. A name that has its entry already keeps
it, so this does nothing once that PR has landed:

    python3 -m chipbench.proving.declare chipbench/proving/entries37.json

``entries37.json`` names PR 37's thirteen (the work / wait metrics of
ingest, pack, commit and bind), whose files and reader are in
``layer_metrics/`` and ``readers/`` already. They are not in the real
``BENCHMARK.json`` because ``tests/chipbench/test_chipbench_gang.py``
holds the gang cell's six to the END of ``per_layer``, which no appended
entry keeps (PERF.md section 7.7). Only proving runs in a copy
(``.checkout/``) and ``tests/chipbench/test_chipbench_work_wait.py`` use
this."""

import json
import sys
from pathlib import Path

ENTRY_KEYS = ("name", "unit", "better", "source", "layer", "moves")


def declared(bench: dict, root: Path, names: list, not_in: dict) -> dict:
    """``bench`` with one entry for each of ``names`` appended to
    ``per_layer``, each listing every cell but those ``not_in`` names
    for it (cell -> metrics it cannot report); a name that is declared
    already keeps the entry it has."""
    have = {m["name"] for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    entries = []
    for name in names:
        if name in have:
            continue
        spec = json.loads(
            (root / "chipbench" / "layer_metrics" / f"{name}.json").read_text()
        )
        entry = {key: spec[key] for key in ENTRY_KEYS}
        entry["workloads"] = [
            cell for cell in cells if name not in not_in.get(cell, ())
        ]
        entries.append(entry)
    return dict(bench, per_layer=bench["per_layer"] + entries)


def main() -> int:
    waiting = json.loads(Path(sys.argv[1]).read_text())
    path = Path("BENCHMARK.json")
    before = json.loads(path.read_text())
    bench = declared(before, Path("."),
                     waiting["metrics"], waiting["not_in"])
    path.write_text(json.dumps(bench, indent=1) + "\n")
    more = len(bench["per_layer"]) - len(before["per_layer"])
    print(f"declared {more} more per-layer metrics in {path.resolve()}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
