# makes `python -m tools.dtflint` resolvable; the scripts in this
# directory stay directly runnable (`python tools/serve_smoke.py`)
