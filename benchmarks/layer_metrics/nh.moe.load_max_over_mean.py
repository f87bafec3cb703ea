"""The held experts' largest load over their mean load, mean over the five
expert layers, at the window's last step (gauge
``dps_moe_load_max_over_mean``): 1 is even routing; the busiest expert's
group is what the grouped matmuls wait for. The reading of
``moe.load_max_over_mean`` under a name of its own because a reader declares
its drivers; this model's 8 held experts are 8 of 512, 22 a token."""

LAYER = "expert layer"
UNIT = "ratio"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    return run.edges[1].get("moe_load_max_over_mean")
