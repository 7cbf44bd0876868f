"""One module per configuration ``driver``: set-up, window and check."""
