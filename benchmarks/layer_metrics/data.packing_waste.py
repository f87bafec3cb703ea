"""Share of the generated tokens that is not document text in a row: the
end-of-document separators and the stream's dropped tail
(``data/tokens.py``; the driver's edge carries the dataset's number)."""

LAYER = "data"
UNIT = "fraction"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "images_per_s_per_chip"
DRIVERS = ("sync_mesh_tokens",)
CHIPS = None


def read(run):
    return run.edges[1].get("packing_waste")
