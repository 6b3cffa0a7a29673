"""Traffic generators (the environments, copied from the program so that
a change there cannot move the yardstick) and the mixes that parameterise
them, one ``<mix>.json`` each."""
