"""Edge-parallel relaxation passes per sweep of the SSSP engine: the
engine steps whose light pass ran plus those whose heavy pass ran
(``SSSPResult.light_relax_passes`` and ``heavy_relax_passes``), mean over
the window's sweeps, from the driver's facts. Each pass gathers every
edge slot once per lane. Moves ``teps``.
"""
UNIT = "passes"


def read(run):
    passes = run.facts.get("relax_passes")
    return sum(passes) / len(passes) if passes else None
