"""Number of executables the window job's fit built (engine layer):
its ``compile`` spans, one per dispatch call during which jax reported
a build. Each carries ``executable``, its number in the fit; a span
without it is the older whole-first-epoch ``compile`` and is not one."""


def read(r):
    builds = [s for s in r["facts"].get("spans", [])
              if s["name"] == "compile" and "executable" in s["attrs"]]
    return len(builds) or None
