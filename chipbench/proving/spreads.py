"""Read proving runs kept by runs.py (chiprun_out/<tag>/summary.json)
and print, for each metric, the median and the spread the contract
defines (the distance between the quartiles of
statistics.quantiles(n=4), over the median). Twelve runs are read as two
sets of 6 with the same seeds: each set's spread, the wider, the spread
as the driver's tightness rule reads it (each set without its run
farthest from the median, the two averaged), and the second median over
the first.

    python3 chipbench/proving/spreads.py <tag> [<tag> ...]
"""
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def trimmed(values):
    med = statistics.median(values)
    return spread(sorted(values, key=lambda v: abs(v - med))[:-1])


def main() -> None:
    for tag in sys.argv[1:]:
        with open(f"chiprun_out/{tag}/summary.json") as f:
            runs = [r for r in json.load(f) if r["result"]]
        good = all(r["result"]["correct"] and not r["result"]["failed"]
                   for r in runs)
        print(tag, len(runs), "runs, all correct and none failed:", good)
        for name in runs[0]["result"]["metrics"]:
            v = [r["result"]["metrics"][name]["value"] for r in runs
                 if name in r["result"]["metrics"]]
            print(f"  {name}: {[round(x, 1) for x in v]}")
            if len(v) == 12:
                a, b = v[:6], v[6:]
                print(
                    f"     set 1 median {statistics.median(a):.2f} spread "
                    f"{100 * spread(a):.2f}% | set 2 median "
                    f"{statistics.median(b):.2f} spread {100 * spread(b):.2f}%"
                    f" | trimmed mean {50 * (trimmed(a) + trimmed(b)):.2f}% | "
                    f"second/first {statistics.median(b) / statistics.median(a):.4f}"
                )
            elif len(v) >= 3:
                print(f"     median {statistics.median(v):.2f} spread "
                      f"{100 * spread(v):.2f}%")


if __name__ == "__main__":
    main()
