"""Plain references, one module per model family (`configs/*.json` name
theirs under "reference")."""
