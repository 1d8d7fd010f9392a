"""One general generator per KIND of traffic; a mix is a data file in
``chipbench/traffic/`` that names its generator."""
