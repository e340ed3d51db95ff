"""Share of the edges the dense LT levels swept that carried a live color,
in %.

The window's fused edge visits (an edge counts once at a level when its
source is on the frontier in any color, as IC's counter has it) over its
levels times the edges one dense level sweeps, all summed by the
``build_lt`` driver after the window.  Nothing from a program whose LT
block does not count edge visits."""


def read(run):
    c = run.counters
    fused, levels = c.get("fused_edge_visits"), c.get("levels")
    edges = c.get("edges_per_level")
    if fused is None or fused < 0 or not levels or not edges:
        return None
    return 100.0 * fused / (levels * edges)
