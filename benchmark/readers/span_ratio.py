"""Sum of the attribute ``num`` over the sum of the attribute ``den`` over
the program's spans of one name that started inside the window, times the
configuration's value under ``times_config`` where given.  None where no
such span carries both (a program that does not count them)."""


def read(args, run):
    t0, t1 = run.driver["window_wall"]
    spans = [r for r in run.driver.get("records", [])
             if r.get("kind") == "span" and r.get("name") == args["name"]
             and t0 <= r["ts"] <= t1
             and args["num"] in r and args["den"] in r]
    den = sum(r[args["den"]] for r in spans)
    if not den:
        return None
    scale = (run.cell.config[args["times_config"]]
             if args.get("times_config") else 1.0)
    return scale * sum(r[args["num"]] for r in spans) / den
