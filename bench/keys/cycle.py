"""cycle: every item in turn, from the first; the same order for every seed."""


def draw(params: dict, n_items: int, count: int, seed: int) -> list:
    return [k % n_items for k in range(count)]
