"""``expert_load_max_over_mean.*`` — layer: model step (the expert layer's
routing).

Mean over the window's program calls (decode steps and prefill chunks)
of the fullest held expert's load over the mean load of a held expert,
as the engine observed it from the counts the program made on the device
(``stats()["expert_load_max_over_mean"]``): 1 is an even spread; the
grouped matmul's tiles are as uneven as this."""
from chipbench.layers._stats import delta


def read(result, trace, ctx):
    if "expert_load_max_over_mean" not in result["stats_close"] \
            or "expert_load_max_over_mean" not in result["stats_open"]:
        return None
    n, total = delta(result, "expert_load_max_over_mean")
    return total / n if n else None
