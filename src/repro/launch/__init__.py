"""Entry points: mesh construction, train, serve."""
