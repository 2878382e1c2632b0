"""Mean of the attribute ``attr`` over the program's spans of one name that
started inside the window, over how many entries of the configuration's
list ``per_count_of[0]``, cut to the first ``per_count_of[2]`` (a key of
the configuration: the layers it runs) where that is given, equal
``per_count_of[1]``: a count the program sums over layers of one kind, a
layer.  None where no such span carries the attribute (a program that does
not count it)."""


def read(args, run):
    t0, t1 = run.driver["window_wall"]
    values = [r[args["attr"]] for r in run.driver.get("records", [])
              if r.get("kind") == "span" and r.get("name") == args["name"]
              and t0 <= r["ts"] <= t1 and args["attr"] in r]
    if not values:
        return None
    per = 1
    if args.get("per_count_of"):
        key, kind, *cut = args["per_count_of"]
        cfg = run.cell.config
        entries = cfg[key][:cfg[cut[0]]] if cut else cfg[key]
        per = list(entries).count(kind)
    return sum(values) / len(values) / per
