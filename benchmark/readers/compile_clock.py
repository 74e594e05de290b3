"""A field of the compile clock as it stood when the window opened."""


def read(obs, field):
    return obs.get("compile", {}).get(field)
