"""Program counter: the objective's real document pairs (the sum over
queries of length squared) over the pair slots its layout computes (the
sum over length classes of queries x class length squared), read off the
booster before the first iteration (``objective.pair_layout``)."""


def read(run):
    layout = run.get("rank_layout") or {}
    if not layout.get("computed_pairs"):
        return None
    return 100.0 * layout["real_pairs"] / layout["computed_pairs"]
