"""The tier comparison: every ledger the configuration names
(``expect_tier`` for the batch solver's, ``expect_tiers`` for others)
against what the program booked since the window opened, one line a
ledger. Nothing may have been solved below the expected tier, and
something on it."""

from __future__ import annotations

from chipbench.check import expected_tiers


def run(run, control: bool) -> bool:
    from chipbench.harness import LEDGERS, TIERS

    start, after = run.window_counters, run.counters()
    ok = True
    for ledger, expect in expected_tiers(run.config).items():
        tiers = {
            t: after["tiers"][ledger].get(t, 0) - start["tiers"][ledger].get(t, 0)
            for t in TIERS
        }
        below = sum(tiers[t] for t in TIERS[TIERS.index(expect) + 1:])
        what = "tier: batches" if ledger == "batch" else f"tier {ledger}: solves"
        floor = LEDGERS[ledger][1]
        if floor is not None:  # pods that fell through every tier
            fell = after[floor] - start[floor]
            below += fell
            what = f"tier {ledger}: {floor} {fell}, solves"
        tier_ok = tiers[expect] > 0 and below == 0
        print(f"compare {what} by tier {tiers}, below {expect!r}: "
              f"{below} (limit 0) -> {'ok' if tier_ok else 'FAILED'}",
              flush=True)
        ok &= tier_ok
    return bool(ok)
