"""Client models and the LLM architectures behind them.

Import the module you need (``repro.models.mlp``, ``.lm_client``,
``.model``); the package itself imports nothing, so loading one model
does not load the others.
"""
