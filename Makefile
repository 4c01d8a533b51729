# One-word entry points for the repo's verify loop. The benchmark is
# bench/run.py (BENCHMARK.json); it runs on the chip, not from here.
PY := PYTHONPATH=src$(if $(PYTHONPATH),:$(PYTHONPATH)) python

.PHONY: test lint

# tier-1 verify (ROADMAP.md)
test:
	$(PY) -m pytest -x -q

# pyflakes-critical lint tier (ruff.toml); check-only — CI never autofixes
lint:
	ruff check --no-fix .
