"""moe_held_load_max_over_mean (x): the skew the experts' product sees: the
most assignments any one held expert got in one layer of a unit (the
program's gauge `moe/held_expert_load_max`, a unit's mean) over the mean load
of a held expert (its counter `moe/assignments_held_total` over units, layers
and experts held). 1 is a flat load; the expert loop's trip count follows the
maximum. Layer: expert layer. Moves train_images_per_s."""


def read(run):
    if run.peaks is None:
        return None
    return run.counters.get("held_load_max_over_mean")
