"""Tiles the flash kernels' programs compute over the tiles of the causal
triangle, from ``dps_flash_tiles_total{kind}`` at the window's last edge
(counted as the kernels are traced, so over every call the run's programs
hold: the step's forward, recomputation, dQ and dK/dV of all four layers and
the evaluation's forward): ``(unmasked + masked) / (unmasked + masked +
below_band)``. 1 is plain causal attention; what the window saves shows as
less.

By query-key pairs the four layers need (134.2M + 3 x 58.7M) / (4 x 134.2M) =
0.58 of the triangle. By tiles a little more, because a tile the band's edge
crosses is computed whole: at 16,384 tokens the kernels pick 512-wide blocks
(``pick_block``), a global layer visits 528 tiles a (batch, head) and a
window layer 252 (36 + 24 x 9: 196 unmasked, 32 on the diagonal, 24 on the
band's lower edge; 276 below the band), ``tile_plan``'s count: (528 + 3 x
252) / (4 x 528) = 0.608."""

LAYER = "kernels"
UNIT = "fraction"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_lm",)
CHIPS = None


def read(run):
    tiles = run.edges[1].get("flash_tiles")
    if not tiles:
        return None
    computed = tiles.get("unmasked", 0) + tiles.get("masked", 0)
    triangle = computed + tiles.get("below_band", 0)
    return computed / triangle if triangle else None
