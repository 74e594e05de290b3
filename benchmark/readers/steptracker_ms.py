"""Host ms per step of the named ``StepTracker`` components (the program's
own histograms ``module.step.<component>_ms``, read at the window's two
edges)."""


def read(obs, components):
    parts = obs.get("components_ms")
    if not parts or not obs.get("steps"):
        return None
    return sum(parts[c] for c in components) / obs["steps"]
