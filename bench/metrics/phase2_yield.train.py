"""Exact draws that decide a topic, per exact draw phase 2 made: the mean,
over the window's evaluated iterations in ``fit``'s history, of
(1 - ``frac_skipped``) / ``frac_phase2_slots``. A token that passed the
skip test gets its word's top topic whatever phase 2 draws, so a draw made
for it decides nothing. 100% when phase 2 draws only the survivors."""


def read(ctx):
    vals = [(1.0 - s["frac_skipped"]) / s["frac_phase2_slots"]
            for s in ctx.get("stats", [])
            if "frac_skipped" in s and s.get("frac_phase2_slots", 0) > 0]
    if not vals:
        return None
    return 100.0 * sum(vals) / len(vals)
