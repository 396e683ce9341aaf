from repro.energy.power_model import (A6000, A6000_MEASURED, DEVICE_KINDS,
                                      EDGE_ORIN, H100, HARDWARE,
                                      HW_CONST_COLS, L4, TPU_V5E, DVFSModel,
                                      HardwareSpec, hardware_for_device,
                                      hw_const_rows, parse_fleet_hardware,
                                      resolve_hardware)
from repro.energy.costs import (CostModel, active_param_count,
                                get_cost_model, iteration_cost, param_count)
from repro.energy.phases import phase_optimal_frequencies

__all__ = ["A6000", "A6000_MEASURED", "CostModel", "DEVICE_KINDS",
           "DVFSModel", "EDGE_ORIN", "H100", "HARDWARE", "HW_CONST_COLS",
           "HardwareSpec", "L4", "TPU_V5E", "active_param_count",
           "get_cost_model", "hardware_for_device", "hw_const_rows",
           "iteration_cost", "param_count",
           "parse_fleet_hardware", "phase_optimal_frequencies",
           "resolve_hardware"]
