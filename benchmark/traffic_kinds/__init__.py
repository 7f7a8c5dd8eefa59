"""Traffic kinds: one general generator and driver per kind of load, found
by a traffic file's `kind`. A traffic MIX is a data file of parameters under
`benchmark/traffic/`; a new kind of load is a new module here."""
