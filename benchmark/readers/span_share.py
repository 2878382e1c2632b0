"""Sum of the attribute ``num`` over the sum of the attributes ``of`` (a
list that names ``num`` among them: the parts of one whole the program
counts apart) over the program's spans of one name that started inside the
window.  None where no such span carries them all (a program that does not
count them) or the whole is 0."""


def read(args, run):
    t0, t1 = run.driver["window_wall"]
    spans = [r for r in run.driver.get("records", [])
             if r.get("kind") == "span" and r.get("name") == args["name"]
             and t0 <= r["ts"] <= t1 and all(k in r for k in args["of"])]
    whole = sum(r[k] for r in spans for k in args["of"])
    if not whole:
        return None
    return sum(r[args["num"]] for r in spans) / whole
